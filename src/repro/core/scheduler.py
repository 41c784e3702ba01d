"""Task-restructuring patterns from the paper's evaluation (§5).

The paper's methodology for porting task-based OpenMP programs to cluster
devices distills into three reusable scheduling patterns, implemented here as
*thin builders* that lower into the unified :class:`~repro.core.taskgraph.
TaskGraph` IR (one node per offloaded region) and run through
:func:`~repro.core.taskgraph.run_graph`:

* **Strip partitioning** (alignment §5.3, mandelbrot §5.4): split an index
  space into per-device strips, offload each as a ``nowait`` target region
  with array sections, stitch the results.
* **Recursive unroll-then-offload** (fib §5.5): OpenMP forbids device→device
  work forwarding, so the host expands the task recursion until the frontier
  has (at least) one task per device, offloads the subtrees, and combines.
* **Wavefront with host-mediated dependencies** (sparselu §5.6): a task DAG
  where every inter-device dependency must round-trip through the host —
  the pattern the paper shows does NOT pay on a slow link.

Because the patterns share one executor, they inherit ``nowait``/
``resident``/``peer`` composition and pluggable *placement policies*
(``policy="round-robin" | "locality" | "heft"`` or a
:class:`~repro.core.taskgraph.PlacementPolicy` instance) instead of each
hard-coding round-robin dispatch.

Beyond-paper: speculative re-dispatch of straggler strips (the paper observes
fib's imbalance but offers no mitigation), and comm-aware device selection.
"""
from __future__ import annotations

import concurrent.futures as _cf
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .target import MapSpec, TargetExecutor, TargetFuture
from .taskgraph import (PeerRef, PlacementContext, TaskGraph, TaskNode,
                        resolve_policy, run_graph)


# ---------------------------------------------------------------------------
# Strip partitioning
# ---------------------------------------------------------------------------
def strip_partition(total: int, n_devices: int) -> List[Tuple[int, int]]:
    """Split ``range(total)`` into ≤n_devices contiguous (start, length) strips.

    Remainder elements go to the leading strips, so strip lengths differ by at
    most 1 (paper Listing 2 uses equal strips; we generalize to any total).
    """
    if total <= 0 or n_devices <= 0:
        return []
    n = min(total, n_devices)
    base, rem = divmod(total, n)
    strips, start = [], 0
    for i in range(n):
        length = base + (1 if i < rem else 0)
        strips.append((start, length))
        start += length
    return strips


def _strip_nodes(kernel: str, strips: List[Tuple[int, int]],
                 make_maps: Callable[[int, int], MapSpec],
                 tags: List[str]) -> List[TaskNode]:
    return [TaskNode(name=f"strip{i}", kernel=kernel,
                     make_maps=(lambda s=start, l=length:
                                lambda deps: make_maps(s, l))(),
                     tag=tags[i])
            for i, (start, length) in enumerate(strips)]


def offload_strips(ex: TargetExecutor, kernel: str, total: int,
                   make_maps: Callable[[int, int], MapSpec], *,
                   combine_axis: int = 0, out_name: str = "out",
                   speculate: bool = False, nowait: bool = True,
                   policy: Any = None, tag: str = "strips") -> jax.Array:
    """The alignment/mandelbrot pattern: one nowait region per device strip.

    ``make_maps(start, length)`` builds the MapSpec for a strip (only the
    needed sections move — paper Listing 2).  Lowers into a single-wave
    :class:`TaskGraph`; ``policy`` picks the device per strip (default
    round-robin, the historical behavior).  With ``speculate=True``, once
    every strip has been dispatched the host re-dispatches not-yet-finished
    strips onto devices that already returned (straggler mitigation;
    first-completed result wins) — the one pattern piece that cannot be
    wave-synchronous, so it shares the graph's *placement* but keeps its own
    harvest loop.
    """
    strips = strip_partition(total, len(ex.pool))
    orig_tags = [f"{tag}[{start}:{start+length}]" for start, length in strips]
    nodes = _strip_nodes(kernel, strips, make_maps, orig_tags)
    if not speculate or not nowait:
        # NOTE ``nowait=False`` keeps serial dispatch (and wins over
        # ``speculate`` — there is no straggler to race when strips run one
        # at a time): the benchmarks use it so per-task compute times are
        # uncontended on this 1-core container; the CostModel supplies the
        # parallel makespan (devices modeled concurrent).
        res = run_graph(ex, TaskGraph(nodes), policy=policy,
                        out_name=out_name, nowait=nowait, tag=tag)
        return _gather([res[n.name] for n in nodes], combine_axis)
    pol = resolve_policy(policy)
    D = len(ex.pool)
    ctx = PlacementContext(pool=ex.pool, cost=ex.pool.cost, D=D)
    pol.begin(ctx)
    futs: List[TargetFuture] = []
    for i, (start, length) in enumerate(strips):
        dev = pol.place(ctx, nodes[i], i, orig_tags[i])
        if not (0 <= dev < D):
            raise ValueError(f"policy {pol.name!r} placed strip {i} on "
                             f"device {dev} of {D}")
        ctx.load[dev] = ctx.load.get(dev, 0) + 1
        ctx.home[nodes[i].name] = dev
        futs.append(ex.target(kernel, dev, make_maps(start, length),
                              nowait=True, tag=orig_tags[i]))
    respawned: Dict[int, TargetFuture] = {}
    try:
        results = _speculative_harvest(ex, kernel, strips, make_maps,
                                       futs, respawned, orig_tags, tag)
    finally:
        # a failed strip propagates, but every dispatched future must be
        # unregistered either way (they are settled or abandoned here)
        ex.retire(futs)
        ex.retire(list(respawned.values()))
    return _gather([r[out_name] for r in results], combine_axis)


def _gather(parts: List[jax.Array], axis: int) -> jax.Array:
    """The strips joined along ``axis`` where the first one lies: strips
    computed on other chips are copied there first."""
    home = parts[0].sharding
    return jnp.concatenate([jax.device_put(p, home) for p in parts],
                           axis=axis)


def _speculative_harvest(ex: TargetExecutor, kernel: str,
                         strips: List[Tuple[int, int]],
                         make_maps: Callable[[int, int], MapSpec],
                         futs: List[TargetFuture],
                         respawned: Dict[int, TargetFuture],
                         orig_tags: List[str], tag: str):
    results: List[Optional[Dict[str, jax.Array]]] = [None] * len(strips)
    pending = set(range(len(strips)))
    # Wait for the first completion, harvest everything done by then, and
    # re-dispatch the stragglers on freed devices (round-robin).  Without the
    # wait the harvest races the dispatch loop and finds nothing "already
    # returned", so no straggler is ever respawned.
    _cf.wait([f._fut for f in futs], return_when=_cf.FIRST_COMPLETED)
    done_devices: List[int] = []
    for i in list(pending):
        if futs[i].done():
            results[i] = futs[i].result()
            pending.discard(i)
            done_devices.append(i)
    spec_tags: Dict[int, str] = {}
    for j, i in enumerate(list(pending)):
        if done_devices:
            dev = done_devices[j % len(done_devices)]
            start, length = strips[i]
            spec_tags[i] = f"{tag}:spec[{i}]"
            respawned[i] = ex.target(kernel, dev, make_maps(start, length),
                                     nowait=True, tag=spec_tags[i])
    for i in list(pending):
        # take whichever copy finishes first (genuine first-completed wait,
        # not an instant done() peek the respawn could never win); a failed
        # copy only surfaces if the other copy cannot produce a result
        if i in respawned:
            pair = (futs[i], respawned[i])
            done, _ = _cf.wait([f._fut for f in pair],
                               return_when=_cf.FIRST_COMPLETED)
            first = pair[0] if pair[0]._fut in done else pair[1]
            other = pair[1] if first is pair[0] else pair[0]
            try:
                results[i] = first.result()
            except Exception:
                results[i] = other.result()   # both failed → this re-raises
        else:
            results[i] = futs[i].result()
    # Settle BOTH copies of every duplicated strip BEFORE striking the losing
    # copy's compute + transfers from the cost model — a discard issued while
    # the loser still runs would miss its late records and leave phantom work
    # inflating the modeled makespan.  ``discard_tag`` strikes EVERY record
    # lane carrying the loser's tag — funnel transfers, compute, adjustments
    # AND peer SEND/RECV records (regions whose inputs rode the peer fabric
    # tag those edges per region, so the strike reaches them).
    for i, spec_fut in respawned.items():
        try:
            spec_out = spec_fut.result()
        except Exception:
            spec_out = None              # failed respawn: original won
        won_spec = spec_out is not None and results[i] is spec_out
        if won_spec:
            try:
                futs[i].result()         # settle the losing original
            except Exception:
                pass                     # loser failed after losing: moot
        # else: the original was settled by the selection loop
        ex.pool.cost.discard_tag(orig_tags[i] if won_spec else spec_tags[i])
        if won_spec:
            # canonicalize the winner onto the strip's own tag: the model
            # must read the same whichever copy won the race (asserted by
            # the no-op-speculation test), and downstream consumers
            # (placement_report, discard by region) key on the strip tag
            ex.pool.cost.rename_tag(spec_tags[i], orig_tags[i])
    return results


# ---------------------------------------------------------------------------
# Recursive unroll-then-offload (fib pattern)
# ---------------------------------------------------------------------------
@dataclass
class RecursiveTask:
    payload: Any
    depth: int = 0


def recursive_offload(ex: TargetExecutor, kernel: str,
                      root: Any,
                      split: Callable[[Any], Optional[List[Any]]],
                      host_combine: Callable[[Any, List[Any]], Any],
                      make_maps: Callable[[Any], MapSpec], *,
                      out_name: str = "out", nowait: bool = True,
                      policy: Any = None, tag: str = "rec") -> Any:
    """Expand the recursion on the host until ≥1 task per device, then offload.

    Paper §5.5: "the host executes the first recursive calls. When the
    recursion unwinds to the point where the number of generated tasks is
    equal to the number of available devices, the host can offload the tasks
    to the devices and wait for their results."

    ``split(payload)`` returns child payloads (or None at a leaf);
    ``host_combine(payload, child_results)`` folds children back up the tree.
    The frontier lowers into a single-wave :class:`TaskGraph` (``policy``
    places it; default round-robin, the paper's one-task-per-device).
    """
    n_dev = len(ex.pool)

    # BFS frontier expansion, tracking the tree for the combine phase.
    class _Node:
        __slots__ = ("payload", "children", "result")

        def __init__(self, payload):
            self.payload, self.children, self.result = payload, [], None

    root_node = _Node(root)
    frontier = [root_node]
    while len(frontier) < n_dev:
        # expand the node whose subtree is largest — payload-agnostic: FIFO
        node = frontier.pop(0)
        kids = split(node.payload)
        if kids is None:           # leaf reached before enough parallelism
            node.result = None
            frontier.append(node)  # will be offloaded as-is
            if all(split(n.payload) is None for n in frontier):
                break
            continue
        node.children = [_Node(k) for k in kids]
        frontier.extend(node.children)

    # Offload the frontier as one graph wave (paper: one task per device; if
    # the tree yields more tasks than devices the policy spreads them —
    # round-robin by default, imbalance noted in the paper).
    gnodes = [TaskNode(name=f"leaf{i}", kernel=kernel,
                       make_maps=(lambda p=node.payload:
                                  lambda deps: make_maps(p))(),
                       tag=f"{tag}[{i}]")
              for i, node in enumerate(frontier)]
    res = run_graph(ex, TaskGraph(gnodes), policy=policy, out_name=out_name,
                    nowait=nowait, tag=tag)
    for i, node in enumerate(frontier):
        node.result = res[f"leaf{i}"]

    # Host-side combine, bottom-up.
    def fold(node: _Node) -> Any:
        if not node.children:
            return node.result
        return host_combine(node.payload, [fold(c) for c in node.children])

    return fold(root_node)


# ---------------------------------------------------------------------------
# Wavefront DAG with host-mediated dependencies (sparselu pattern)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DagTask:
    name: str
    kernel: str
    deps: Tuple[str, ...]
    make_maps: Callable[[Dict[str, Any]], MapSpec]   # dep results -> maps
    device: Optional[int] = None                      # None = policy picks


def wavefront_offload(ex: TargetExecutor, tasks: Sequence[DagTask], *,
                      out_name: str = "out", nowait: bool = True,
                      resident: bool = False, peer: bool = False,
                      transport: Optional[Any] = None,
                      policy: Any = None,
                      tag: str = "dag", **graph_kw) -> Dict[str, Any]:
    """Run a dependency DAG where every edge crosses the host (OpenMP rule).

    Thin builder: lowers the :class:`DagTask` list into a
    :class:`~repro.core.taskgraph.TaskGraph` and runs it through
    :func:`~repro.core.taskgraph.run_graph`, which owns the wave dispatch,
    the ``resident`` per-wave pinning, the ``peer`` edge routing and the
    placement ``policy`` — see its docstring for the full semantics.  Tasks
    whose dependencies are satisfied run as concurrent nowait regions, one
    wave at a time; by default each inter-device value is fetched to the
    host and re-sent to the consumer — the comm pattern that makes sparselu
    lose (paper §5.6: "the whole array must be transferred two times").

    ``peer=True`` (beyond-paper) retires that funnel for the DAG's internal
    edges (outputs stay resident via ``device_out``, consumers bind
    ``present`` maps, cross-device edges move once device→device over
    ``transport``); ``resident=True`` pins the wave's shared plain inputs
    once per device per wave; ``policy`` replaces round-robin placement with
    locality- or cost-driven choices (``"locality"``, ``"heft"``, or any
    :class:`~repro.core.taskgraph.PlacementPolicy`) — results are
    bit-identical under every policy, only the traffic changes.

    Extra keyword arguments (``stragglers``, ``checkpoint``,
    ``resume_from``, ``max_retries``) pass through to
    :func:`~repro.core.taskgraph.run_graph` unchanged.
    """
    graph = TaskGraph.from_tasks(tasks)
    return run_graph(ex, graph, policy=policy, out_name=out_name,
                     nowait=nowait, resident=resident, peer=peer,
                     transport=transport, tag=tag, **graph_kw)
