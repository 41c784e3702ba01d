"""Reduce the runtime's own host spans in a profiler trace to layer numbers.

    python3 bench/host_spans.py <trace dir or .xplane.pb>

reads a trace that ``bench/run.py --trace 1 --trace-dir <dir>`` kept and
prints one JSON object.  The runtime wraps each layer boundary in a span
named ``omp.*`` (``src/repro/core/spans.py``); they sit on the
``/host:CPU`` plane, one line per thread, on the chips' clock.  This
module reads them, inside the window ``bench/trace_reduce.py`` takes (first
``solve`` start to last ``solve`` end):

- per span name: how many overlap the window, their total seconds, and
  their self seconds: the duration less the time covered by ``omp.*``
  spans nested in it on the same thread (JAX's own events inside a span
  are part of it);
- the busiest thread's union of ``omp.cmd.*`` spans: how long one device
  worker was serving commands;
- ``idle_by_span``: each idle gap of each chip goes to the span name whose
  self intervals, on any host thread, cover most of it; working spans
  first, spans that wait (``WAITS``) where no working span covers more of
  the gap than the part no ``omp.*`` span covers.  Where that part is the
  larger, the gap goes to ``solve``, ``between`` or ``outside`` as
  ``trace_reduce`` labels it.  Seconds are summed per label.

A trace without ``omp.*`` spans (a program that has none) reduces to empty
numbers and labels every gap the ``trace_reduce`` way.
"""
from __future__ import annotations

import heapq
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

PREFIX = "omp."
CMD_PREFIX = "omp.cmd."
WAVE_PLAN, WAVE_ISSUE, WAVE_JOIN = "omp.wave.plan", "omp.wave.issue", "omp.wave.join"
REGION, REGION_EXEC_WAIT = "omp.region", "omp.region.exec_wait"
EXEC_DISPATCH, EXEC_SYNC = "omp.exec.dispatch", "omp.exec.sync"
#: spans in which a thread blocks rather than works
WAITS = (WAVE_JOIN, REGION_EXEC_WAIT, EXEC_SYNC)

Piece = Tuple[float, float, str]             # (start, end, span name)


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0


@dataclass
class HostSpans:
    window_s: float
    solves: int
    spans: Dict[str, SpanStats] = field(default_factory=dict)
    busiest_cmd_s: float = 0.0               # one thread's omp.cmd.* union
    idle_by_span: List[Tuple[str, float]] = field(default_factory=list)
    idle_s: float = 0.0                      # all chips' idle, summed

    def stat(self, name: str) -> SpanStats:
        return self.spans.get(name, SpanStats())

    def metrics(self) -> Dict[str, Optional[float]]:
        """The layer numbers; None where the trace holds no such span."""
        def per(num: float, den: float) -> Optional[float]:
            return 1e3 * num / den if den else None

        plan, issue = self.stat(WAVE_PLAN), self.stat(WAVE_ISSUE)
        region, wait = self.stat(REGION), self.stat(REGION_EXEC_WAIT)
        disp, sync = self.stat(EXEC_DISPATCH), self.stat(EXEC_SYNC)
        has_waves = plan.count + issue.count > 0
        return {
            "graph_self_ms_per_solve": (per(plan.self_s + issue.self_s,
                                            self.solves)
                                        if has_waves else None),
            "wave_join_ms_per_solve": (per(self.stat(WAVE_JOIN).total_s,
                                           self.solves)
                                       if has_waves else None),
            "region_self_ms": per(region.self_s, region.count),
            "region_exec_wait_ms": per(wait.total_s, wait.count),
            "exec_dispatch_ms": per(disp.self_s, disp.count),
            "exec_sync_ms": per(sync.total_s, sync.count),
            "worker_busy_share": (self.busiest_cmd_s / self.window_s
                                  if self.busiest_cmd_s else None),
            "idle_on_omp_share": (sum(s for n, s in self.idle_by_span
                                      if n.startswith(PREFIX)) / self.idle_s
                                  if self.idle_s else None),
        }

    def as_json(self) -> Dict:
        return {
            "window_s": self.window_s, "solves": self.solves,
            "metrics": self.metrics(),
            "spans": {n: {"count": s.count,
                          "total_ms_per_solve": 1e3 * s.total_s / self.solves,
                          "self_ms_per_solve": 1e3 * s.self_s / self.solves}
                      for n, s in sorted(self.spans.items())},
            "idle_s": self.idle_s,
            "idle_by_span": [[n, s] for n, s in self.idle_by_span]}


def line_pieces(events: List[Piece]) -> Tuple[List[Piece], List[Piece]]:
    """(spans, self pieces) of one thread's ``omp.*`` events: a span's self
    pieces are its interval less its nested ``omp.*`` spans."""
    evs = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    children: List[List[Tuple[float, float]]] = [[] for _ in evs]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append((s, min(e, evs[stack[-1]][1])))
        stack.append(i)
    pieces = [(gs, ge, name) for (s, e, name), kids in zip(evs, children)
              for gs, ge in tr.gaps(tr.union(kids), s, e) if ge > gs]
    return evs, pieces


def _host_lines(pd) -> List[List[Piece]]:
    plane = pd.find_plane_with_name(tr.HOST_PLANE)
    if plane is None:
        return []
    out = []
    for line in plane.lines:
        evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in line.events if ev.name.startswith(PREFIX)]
        if evs:
            out.append(evs)
    return out


def _chip_gaps(pd, lo: float, hi: float) -> List[List[Tuple[float, float]]]:
    """Per chip, the parts of [lo, hi) with no operation on it (as
    ``trace_reduce.reduce`` computes them)."""
    out = []
    for plane in pd.planes:
        if not tr.DEVICE_PLANE.fullmatch(plane.name):
            continue
        ops = (list(tr._events(plane, tr.OP_LINE))
               or list(tr._events(plane, tr.MODULE_LINE)))
        busy = tr.union(tr.clip(((s, e) for _, s, e in ops), lo, hi))
        out.append(tr.gaps(busy, lo, hi))
    return out


def _covered(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in tr.union(intervals))


def label_gaps(gaps: List[Tuple[float, float]], pieces: List[Piece],
               fallback: List[Tuple[float, float, str]]) -> List[str]:
    """The label of each gap (sorted, disjoint): the working span name whose
    self pieces cover most of it, else the waiting one, unless the part no
    ``omp.*`` piece covers is larger; then ``trace_reduce``'s host-span
    label."""
    pieces = sorted(pieces)
    live: List[Tuple[float, float, str]] = []     # heap by end
    k, labels = 0, []
    for g0, g1 in gaps:
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, name = pieces[k]
            heapq.heappush(live, (e, s, name))
            k += 1
        while live and live[0][0] <= g0:
            heapq.heappop(live)
        parts: Dict[str, List[Tuple[float, float]]] = {}
        for e, s, name in live:
            if s < g1:
                parts.setdefault(name, []).append((max(s, g0), min(e, g1)))
        cover = {n: _covered(iv) for n, iv in parts.items()}
        bare = (g1 - g0) - _covered([i for iv in parts.values() for i in iv])
        label = None
        for group in ({n: c for n, c in cover.items() if n not in WAITS},
                      {n: c for n, c in cover.items() if n in WAITS}):
            best = max(group, key=lambda n: (group[n], n), default=None)
            if best is not None and group[best] >= bare:
                label = best
                break
        labels.append(label or tr._label((g0, g1), fallback))
    return labels


def reduce(pd, *, solve_span: str = "solve", between_span: str = "between",
           top: int = 10) -> Optional[HostSpans]:
    """The host-span numbers of one traced window; ``None`` without a
    solve span."""
    fallback = tr.host_spans(pd, (solve_span, between_span))
    solves = [(s, e) for s, e, n in fallback if n == solve_span]
    if not solves:
        return None
    lo, hi = solves[0][0], solves[-1][1]
    out = HostSpans(window_s=(hi - lo) / 1e9, solves=len(solves))
    pieces: List[Piece] = []
    for events in _host_lines(pd):
        evs, own = line_pieces(events)
        for s, e, name in evs:
            for cs, ce in tr.clip([(s, e)], lo, hi):
                st = out.spans.setdefault(name, SpanStats())
                st.count += 1
                st.total_s += (ce - cs) / 1e9
        for s, e, name in own:
            for cs, ce in tr.clip([(s, e)], lo, hi):
                out.spans[name].self_s += (ce - cs) / 1e9
        pieces.extend(own)
        busy = tr.union(tr.clip(((s, e) for s, e, n in evs
                                 if n.startswith(CMD_PREFIX)), lo, hi))
        out.busiest_cmd_s = max(out.busiest_cmd_s,
                                sum(e - s for s, e in busy) / 1e9)
    by_label: Dict[str, float] = {}
    for gaps in _chip_gaps(pd, lo, hi):
        for (g0, g1), name in zip(gaps, label_gaps(gaps, pieces, fallback)):
            by_label[name] = by_label.get(name, 0.0) + (g1 - g0) / 1e9
            out.idle_s += (g1 - g0) / 1e9
    out.idle_by_span = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb file")
    args = ap.parse_args(argv)
    path = (args.trace if os.path.isfile(args.trace)
            else tr.find_xplane(args.trace))
    spans = reduce(tr.load(path))
    if spans is None:
        print(f"{path}: no solve span", file=sys.stderr)
        return 1
    print(json.dumps(spans.as_json()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
