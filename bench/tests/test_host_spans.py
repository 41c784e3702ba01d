"""The host-span reduction (``bench/host_spans.py``): self time with nested
``omp.*`` and foreign children, the busiest worker's union, idle gaps put
down to spans; the existing reduction unchanged by the new spans; the
EXEC-part readers; and one trace recorded on the chip."""
import gzip
import os
import re
from types import SimpleNamespace

import pytest

from bench import host_spans as hs
from bench import spec
from bench import trace_reduce as tr
from bench.tests.conftest import small_cell
from bench.tests.test_trace_reduce import _Plane, _profile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP_TRACE = os.path.join(DATA, "sparselu-k4-tpu-v5e.xplane.pb.gz")


def test_span_names_are_the_runtimes():
    from repro.core import spans
    assert hs.PREFIX == spans.PREFIX
    assert (hs.WAVE_PLAN, hs.WAVE_ISSUE, hs.WAVE_JOIN) == \
        (spans.WAVE_PLAN, spans.WAVE_ISSUE, spans.WAVE_JOIN)
    assert (hs.REGION, hs.REGION_EXEC_WAIT) == \
        (spans.REGION, spans.REGION_EXEC_WAIT)
    assert (hs.EXEC_DISPATCH, hs.EXEC_SYNC) == \
        (spans.EXEC_DISPATCH, spans.EXEC_SYNC)
    assert all(v.startswith(hs.CMD_PREFIX) for v in spans.CMD.values())


def test_self_time_subtracts_nested_spans():
    _, pieces = hs.line_pieces([
        (0, 100, "omp.region"), (20, 60, "omp.region.exec_wait")])
    assert [p for p in pieces if p[2] == "omp.region"] == \
        [(0, 20, "omp.region"), (60, 100, "omp.region")]
    assert [p for p in pieces if p[2] == "omp.region.exec_wait"] == \
        [(20, 60, "omp.region.exec_wait")]


def test_self_time_three_deep():
    _, pieces = hs.line_pieces([
        (0, 100, "omp.cmd.EXEC"), (10, 50, "omp.exec.dispatch"),
        (20, 30, "omp.jit.build"), (50, 95, "omp.exec.sync")])
    own = {}
    for s, e, n in pieces:
        own[n] = own.get(n, 0) + e - s
    assert own == {"omp.cmd.EXEC": 15, "omp.exec.dispatch": 30,
                   "omp.jit.build": 10, "omp.exec.sync": 45}


def _with_spans(extra_lines):
    pd = _profile()
    host = pd.planes[0]
    pd.planes[0] = _Plane("/host:CPU",
                          [(l.name, [(e.name, e.start_ns, e.duration_ns)
                                     for e in l.events])
                           for l in host.lines] + extra_lines)
    return pd


# the synthetic window is [1000, 10000); the chip is busy [1000, 1200),
# [1500, 2500), [6000, 9000), so its gaps are [1200, 1500), [2500, 6000),
# [9000, 10000)
SOLVER = ("solver", [("omp.wave", 1000, 8500), ("omp.wave.plan", 1000, 500),
                     ("omp.wave.issue", 1500, 500),
                     ("omp.wave.join", 2000, 7500)])
WORKER = ("worker", [("omp.cmd.EXEC", 1100, 1400),
                     ("omp.exec.dispatch", 1100, 200),
                     ("jit_lu0", 1150, 100),       # a foreign child
                     ("omp.exec.sync", 1300, 1200),
                     ("omp.cmd.FREE", 2600, 400)])
REGIONS = ("region", [("omp.region", 2500, 4000),
                      ("omp.region.exec_wait", 3000, 3400)])


def test_reduce_counts_total_and_self_in_the_window():
    r = hs.reduce(_with_spans([SOLVER, WORKER, REGIONS]))
    assert r.solves == 2 and r.window_s == 9000 / 1e9
    assert r.stat("omp.wave").total_s == 8500 / 1e9
    assert r.stat("omp.wave").self_s == 0
    assert r.stat("omp.wave.join").self_s == 7500 / 1e9
    assert r.stat("omp.cmd.EXEC").self_s == 0
    assert r.stat("omp.cmd.EXEC").count == 1
    # the foreign child stays part of the dispatch
    assert r.stat("omp.exec.dispatch").self_s == 200 / 1e9
    assert r.stat("omp.region").self_s == 600 / 1e9
    m = r.metrics()
    assert m["graph_self_ms_per_solve"] == pytest.approx(1e3 * 1000e-9 / 2)
    assert m["wave_join_ms_per_solve"] == pytest.approx(1e3 * 7500e-9 / 2)
    assert m["region_self_ms"] == pytest.approx(1e3 * 600e-9)
    assert m["exec_dispatch_ms"] == pytest.approx(1e3 * 200e-9)
    assert m["exec_sync_ms"] == pytest.approx(1e3 * 1200e-9)
    # worker: [1100, 2500) and [2600, 3000)
    assert r.busiest_cmd_s == 1800 / 1e9
    assert m["worker_busy_share"] == pytest.approx(1800 / 9000)


def test_idle_gaps_go_to_working_spans_first():
    r = hs.reduce(_with_spans([SOLVER, WORKER, REGIONS]))
    # [1200, 1500): the plan covers 300 and the dispatch 100 (working), the
    # sync 200 (waiting); [2500, 6000): region self covers 500, FREE 400,
    # the join and the region's exec_wait only wait; [9000, 10000): nothing
    # but the join
    assert dict(r.idle_by_span) == pytest.approx({
        "omp.wave.plan": 300e-9, "omp.region": 3500e-9,
        "omp.wave.join": 1000e-9})
    assert r.idle_s == pytest.approx(4800e-9)
    assert r.metrics()["idle_on_omp_share"] == pytest.approx(1.0)


def test_idle_gaps_fall_back_to_solve_and_between():
    r = hs.reduce(_profile())
    assert dict(r.idle_by_span) == pytest.approx({"solve": 4800e-9})
    assert r.spans == {} and r.busiest_cmd_s == 0
    m = r.metrics()
    assert m.pop("idle_on_omp_share") == 0.0
    assert all(v is None for v in m.values())
    gaps = [(0, 5), (5100, 5200), (20000, 20001)]
    spans = [(0, 5000, "solve"), (5000, 5500, "between")]
    assert hs.label_gaps(gaps, [], spans) == ["solve", "between", "outside"]


def test_a_gap_mostly_outside_every_span_keeps_its_host_label():
    spans = [(0, 5000, "solve"), (5000, 5500, "between")]
    gap = [(4990, 5400)]            # 10 in a region's tail, 400 bare
    tail = [(4000, 5000, "omp.region")]
    assert hs.label_gaps(gap, tail, spans) == ["between"]
    # the same gap covered by a waiting span and a little work
    joined = tail + [(4990, 5400, "omp.wave.join")]
    assert hs.label_gaps(gap, joined, spans) == ["omp.region"]
    # a waiting span alone that covers more than the bare part
    waiting = [(4990, 5300, "omp.exec.sync")]
    assert hs.label_gaps(gap, waiting, spans) == ["omp.exec.sync"]


def test_the_existing_reduction_ignores_the_new_spans():
    plain = tr.reduce(_profile())
    spanned = tr.reduce(_with_spans([SOLVER, WORKER, REGIONS]))
    assert spanned == plain
    assert plain.window_s == 9000 / 1e9 and plain.solves == 2
    assert plain.busy_s == [4200 / 1e9]
    assert plain.device_ops == [("jit_b", 3000 / 1e9), ("jit_a", 1200 / 1e9)]
    assert plain.idle_gaps == [("solve", 3500 / 1e9), ("solve", 1000 / 1e9),
                               ("solve", 300 / 1e9)]
    ctx = SimpleNamespace(trace=spanned, solves=2)
    cell = small_cell("sparselu-bots-b100.1chip")
    read = {n: spec.metric_reader(cell, n).read(ctx)
            for n in ("device_idle", "device_ms_per_solve")}
    assert read == {"device_idle": pytest.approx(1 - 4200 / 9000),
                    "device_ms_per_solve": pytest.approx(1e3 * 4200e-9 / 2)}


PARTS = {"exec_dispatch_ms": "dispatch_s", "exec_sync_ms": "sync_s",
         "exec_gate_ms": "gate_s", "exec_queue_ms": "queue_s"}


@pytest.mark.parametrize("metric", sorted(PARTS))
def test_exec_part_readers(metric):
    reader = spec.metric_reader(small_cell("sparselu-bots-b100.1chip"), metric)
    recs = [SimpleNamespace(seconds=1.0, **{PARTS[metric]: v})
            for v in (0.001, 0.003)]
    assert reader.read(SimpleNamespace(compute=recs)) == pytest.approx(2.0)
    # a program that records no such part: nothing to read
    bare = [SimpleNamespace(seconds=1.0)] * 2
    assert reader.read(SimpleNamespace(compute=bare)) is None
    assert reader.read(SimpleNamespace(compute=[])) is None


# ---------------------------------------------------------------------------
# a trace recorded on the chip (bench/tests/record_chip_trace.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData
    with gzip.open(CHIP_TRACE, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_the_chip_trace_has_the_planes_and_lines_the_reduction_reads(chip_trace):
    names = {p.name for p in chip_trace.planes}
    assert "/host:CPU" in names and "/device:TPU:0" in names
    chip = chip_trace.find_plane_with_name("/device:TPU:0")
    assert {tr.OP_LINE, tr.MODULE_LINE} <= {l.name for l in chip.lines}
    host = chip_trace.find_plane_with_name("/host:CPU")
    seen = {ev.name for l in host.lines for ev in l.events
            if ev.name.startswith("omp.") or ev.name in ("solve", "between")}
    assert {"solve", "between", "omp.wave", "omp.wave.plan", "omp.wave.issue",
            "omp.wave.join", "omp.region", "omp.region.exec_wait",
            "omp.cmd.ALLOC", "omp.cmd.XFER_TO", "omp.cmd.EXEC",
            "omp.cmd.WRITEBACK", "omp.cmd.FREE", "omp.exec.dispatch",
            "omp.exec.sync"} <= seen


def test_the_chip_trace_reduces(chip_trace):
    r = tr.reduce(chip_trace)
    assert r is not None and r.solves >= 1
    assert 0 < r.idle_share < 1
    # module names carry a hash: jit_lu0(<digits>)
    kernels = {re.sub(r"\(\d+\)$", "", n) for n, _ in r.device_ops}
    assert {"jit_lu0", "jit_fwd", "jit_bdiv", "jit_bmod"} <= kernels
    assert not any(n.startswith("jit__lambda") for n in kernels)
    h = hs.reduce(chip_trace)
    m = h.metrics()
    for k in ("graph_self_ms_per_solve", "region_self_ms", "exec_dispatch_ms",
              "exec_sync_ms", "worker_busy_share", "idle_on_omp_share"):
        assert m[k] is not None and m[k] > 0, k
    assert m["worker_busy_share"] <= 1
    assert sum(s for _, s in h.idle_by_span) <= h.idle_s * (1 + 1e-9)
