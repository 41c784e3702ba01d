"""Unified TaskGraph IR + cost-driven placement policies.

The paper's three restructuring patterns (strips §5.3–5.4, recursive unroll
§5.5, wavefront §5.6) each used to carry their own dispatch loop and their
own static device choice (round-robin over arrival order) — blind to where
the data already lives and to what the links cost.  §5.6's lesson is that
the wavefront loses exactly when dependencies cross devices; the OpenMP
Cluster model (arXiv:2207.05677) and HDArray (arXiv:1809.05657) both answer
by lowering everything to one task-graph representation scheduled by a
cost-aware policy.  This module is that layer:

* :class:`TaskNode` / :class:`TaskGraph` — the IR.  A node names its kernel,
  its dependency edges (producer task names), the logical buffer names it
  reads/writes, and a ``make_maps`` callback producing the region's
  :class:`~repro.core.target.MapSpec` from its dependencies' values.
* :func:`run_graph` — the one executor every pattern lowers into: waves of
  ready nodes dispatched as ``nowait`` regions, with per-wave resident pins
  (``resident=True``) and device→device edge routing (``peer=True``)
  inherited by *all* patterns instead of re-implemented per pattern.
* :class:`PlacementPolicy` — who decides where a node runs:

  - :class:`RoundRobin` — arrival order modulo device count (the historical
    behavior, and the baseline every policy is judged against),
  - :class:`LocalityAffinity` — prefer the device already holding the node's
    inputs (producer homes and present-table residents), tie-break by the
    wave's queue depth,
  - :class:`HeftPlacement` — earliest-finish-time list scheduling: per-device
    ready clocks, observed kernel timings (:meth:`CostModel.kernel_time`),
    and per-dependency edge costs under the transport's link model, choosing
    host-funnel vs peer routing per edge and logging each prediction for
    :meth:`CostModel.placement_report`.

Placement never changes *values* — every policy runs the same kernels on the
same operands, so results are bit-identical across policies (property-tested)
— it changes which bytes move over which wire.
"""
from __future__ import annotations

import concurrent.futures as _cf
import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import spans
from .device import DeviceFailure
from .spans import span
from .target import (MapSpec, Section, TargetExecutor, TargetFuture,
                     _alias_map, _flatten_map_value)
from .transport import HostFunnelTransport


# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PeerRef:
    """A dependency value that lives on a device, not on the host.

    Under ``run_graph(peer=True)`` the ``deps`` dict handed to a node's
    ``make_maps`` holds these placeholders instead of host arrays: a
    callback that treats dependency values *opaquely* (placing them in a
    ``to=`` clause) works unchanged, and the runner rewrites any ``to``
    entry holding a PeerRef into a ``present`` binding.  Resolution is
    placement-independent: the runner locates the producer's *current* home
    through its live producer map, so the same DAG (and the same refs) runs
    under any placement policy.  ``device`` records where the entry lived
    when the ref was minted — informational only, never consulted to route.
    A callback that does arithmetic on dependency values cannot be
    peer-routed (the value genuinely is not on the host).
    """

    task: str
    entry: str
    device: Optional[int] = None


@dataclass(frozen=True)
class TaskNode:
    """One node of the IR: kernel + map-building callback + edge names.

    ``deps`` are producer task names (the dataflow edges); ``reads`` extends
    them with logical buffer names the node consumes without a producer in
    the graph (policies score both for locality); ``writes`` names what it
    produces (defaults to the node's own name — carried for graph
    introspection and future anti-dependency tracking, placement consults
    ``reads`` only).  ``device`` forces placement; ``tag`` overrides the
    region tag (pattern builders use it to keep their historical per-region
    tags).  ``work`` declares what the node's kernel computes (counts by
    name, such as cells and bytes); ``run_graph`` carries it onto the
    EXEC's :class:`~repro.core.costmodel.ComputeRecord`.
    """

    name: str
    kernel: str
    deps: Tuple[str, ...] = ()
    make_maps: Callable[[Dict[str, Any]], MapSpec] = None
    device: Optional[int] = None
    tag: Optional[str] = None
    reads: Tuple[str, ...] = ()
    writes: Tuple[str, ...] = ()
    work: Optional[Dict[str, float]] = None


class TaskGraph:
    """An ordered collection of :class:`TaskNode`\\ s forming a DAG."""

    def __init__(self, nodes: Iterable[Any] = ()) -> None:
        self._nodes: Dict[str, TaskNode] = {}
        for n in nodes:
            self.add(n)

    @classmethod
    def from_tasks(cls, tasks: Iterable[Any]) -> "TaskGraph":
        """Build from anything node-shaped (``TaskNode``, ``DagTask``, …).

        Duck-typed on ``name/kernel/deps/make_maps`` with optional
        ``device/tag/reads/writes/work`` — the lowering entry point the
        pattern builders use.
        """
        g = cls()
        for t in tasks:
            g.add(t)
        return g

    def add(self, node: Any) -> TaskNode:
        if not isinstance(node, TaskNode):
            node = TaskNode(
                name=node.name, kernel=node.kernel,
                deps=tuple(node.deps), make_maps=node.make_maps,
                device=getattr(node, "device", None),
                tag=getattr(node, "tag", None),
                reads=tuple(getattr(node, "reads", ()) or ()),
                writes=tuple(getattr(node, "writes", ()) or ()),
                work=getattr(node, "work", None))
        if node.name in self._nodes:
            raise ValueError(f"duplicate task {node.name!r}")
        if not node.reads:
            node = TaskNode(**{**node.__dict__, "reads": node.deps})
        if not node.writes:
            node = TaskNode(**{**node.__dict__, "writes": (node.name,)})
        self._nodes[node.name] = node
        return node

    @property
    def nodes(self) -> Dict[str, TaskNode]:
        return dict(self._nodes)

    def node(self, name: str) -> TaskNode:
        return self._nodes[name]

    def __len__(self) -> int:
        return len(self._nodes)

    def __iter__(self):
        return iter(self._nodes.values())

    def waves(self) -> List[List[str]]:
        """Topological wave decomposition (raises on cycles/missing deps)."""
        done: set = set()
        remaining = dict(self._nodes)
        out: List[List[str]] = []
        while remaining:
            ready = [n for n in remaining.values()
                     if all(d in done for d in n.deps)]
            if not ready:
                raise ValueError(
                    f"dependency cycle among {sorted(remaining)}")
            out.append([n.name for n in ready])
            for n in ready:
                done.add(n.name)
                del remaining[n.name]
        return out


# ---------------------------------------------------------------------------
# Placement policies
# ---------------------------------------------------------------------------
@dataclass
class PlacementContext:
    """What a policy may look at when placing a node.

    ``home`` maps every already-placed task to its device; ``out_bytes`` to
    its output size (producers placed in earlier waves, or earlier in this
    wave).  ``load`` counts this wave's placements per device (queue depth).
    """

    pool: Any
    cost: Any
    D: int
    peer: bool = False
    transport: Any = None
    home: Dict[str, int] = field(default_factory=dict)
    out_bytes: Dict[str, int] = field(default_factory=dict)
    load: Dict[int, int] = field(default_factory=dict)
    # task -> devices holding a live copy of its output (the home plus every
    # peer-propagated replica).  The runner moves a cross-device edge ONCE
    # per (entry, device) and binds it free afterwards; a cost-aware policy
    # must price repeat edges at zero or it will overestimate spreading.
    replicas: Dict[str, set] = field(default_factory=dict)
    wave: int = 0
    # device indices the pool's HealthRegistry considers placeable this wave
    # (None = no health information: every device is a candidate).  Policies
    # must place only onto these; the runner refreshes the list per wave and
    # after every recovered failure.
    healthy: Optional[List[int]] = None
    # the transport's repro.core.topology.Topology, when it has one (the
    # runner mirrors it here): rack structure + per-pair link costs a
    # policy may query directly — e.g. topology.same_rack(a, b) — beyond
    # what edge pricing already folds in.
    topology: Any = None

    def candidates(self) -> List[int]:
        """The devices a policy may place onto, always non-empty."""
        if self.healthy:
            cands = [d for d in self.healthy if d < self.D]
            if cands:
                return cands
        return list(range(self.D))


class PlacementPolicy:
    """Where does a ready node run, and over which wire do its edges ride."""

    name = "abstract"

    def begin(self, ctx: PlacementContext) -> None:
        """Reset per-run state (policies may be reused across runs)."""

    def place(self, ctx: PlacementContext, node: TaskNode,
              ready_index: int, region_tag: str) -> int:
        raise NotImplementedError

    def route_edge(self, ctx: PlacementContext, src: int, dst: int,
                   nbytes: int) -> str:
        """Which wire carries one cross-device dependency edge:
        ``"peer"`` (raw peer message), ``"peer+int8"`` (peer message under
        the modeled block-int8 wire — chosen by the transport's topology
        where the link's bandwidth-delay arithmetic says the byte savings
        beat the quantize cost), or ``"funnel"`` (fetch + re-send on the
        host NIC).  The base policy defers the peer/compressed choice to
        the transport's own :meth:`~repro.core.transport.Transport.
        edge_route`; without a topology that is always plain ``"peer"``.
        """
        if ctx.transport is not None:
            return ctx.transport.edge_route(ctx.cost, src, dst, nbytes)[1]
        return "peer"


class RoundRobin(PlacementPolicy):
    """Arrival order modulo device count — the historical static placement."""

    name = "round-robin"

    def place(self, ctx: PlacementContext, node: TaskNode,
              ready_index: int, region_tag: str) -> int:
        cands = ctx.candidates()
        if node.device is not None:
            # a forced device is honored while healthy; a blacklisted one
            # falls back to policy placement among the survivors
            if ctx.healthy is None or node.device in cands:
                return node.device
        return cands[ready_index % len(cands)]


class LocalityAffinity(PlacementPolicy):
    """Prefer the device that already holds the node's inputs.

    Scores each device by the bytes of the node's ``reads`` homed there —
    producer outputs via the runner's live placement map, producer-less
    names via the device present tables — and breaks ties by this wave's
    queue depth (then lowest index, for determinism).  With no locality
    signal it degrades to arrival order, i.e. exactly :class:`RoundRobin`.
    """

    name = "locality"

    def place(self, ctx: PlacementContext, node: TaskNode,
              ready_index: int, region_tag: str) -> int:
        cands = ctx.candidates()
        if node.device is not None and (ctx.healthy is None
                                        or node.device in cands):
            return node.device
        score = {d: 0 for d in cands}
        for dep in node.reads:
            if dep in ctx.replicas:
                nb = ctx.out_bytes.get(dep, 0) or 1
                for d in ctx.replicas[dep]:   # home + propagated copies
                    if d in score:            # elastic shrink may strand a
                        score[d] += nb        # replica on a removed index
                continue
            src = ctx.home.get(dep)
            if src is not None:
                if src in score:
                    score[src] += ctx.out_bytes.get(dep, 0) or 1
                continue
            for d in cands:
                e = ctx.pool.present[d].get(dep)
                if e is not None and not e.spilled:
                    score[d] += e.nbytes()
        best = max(score.values())
        if best == 0:
            return cands[ready_index % len(cands)]
        tied = [d for d in cands if score[d] == best]
        return min(tied, key=lambda d: (ctx.load.get(d, 0), d))


class HeftPlacement(PlacementPolicy):
    """Earliest-finish-time placement under the recorded cost model.

    Classic HEFT list scheduling specialized to the wave dispatcher: each
    device carries a modeled ready clock; a node's candidate finish time on
    device ``d`` is ``max(ready[d], latest edge arrival) + est`` where
    ``est`` is the mean observed compute time of the node's kernel
    (:meth:`CostModel.kernel_time`; ``default_task_s`` before any
    observation) and each cross-device edge costs the cheaper of the host
    funnel (fetch + re-send on the NIC) and the peer fabric
    (:meth:`Transport.edge_time`) — the same comparison
    :meth:`route_edge` answers, so the runner moves each dependency over
    the wire the policy priced.  Under a transport with a
    :class:`~repro.core.topology.Topology`, peer edges are priced per
    device pair (fat intra-rack links vs the thin spine), so EFT naturally
    packs hot producer→consumer chains into one rack and routes the edges
    it must send cross-rack as ``"peer+int8"`` where the link favors the
    compressed wire.  Every decision is logged via
    :meth:`CostModel.record_placement` for predicted-vs-observed reports.
    """

    name = "heft"

    def __init__(self, default_task_s: float = 1e-3,
                 use_observed: bool = True,
                 estimates: Optional[str] = None) -> None:
        self.default_task_s = default_task_s
        # use_observed=False freezes the compute estimate at
        # ``default_task_s`` — deterministic placement for tests/benchmarks
        # (measured timings on a shared host include jit-compile spikes that
        # would drown the modeled link and vary run to run)
        self.use_observed = use_observed
        # estimates selects the compute-estimate source explicitly:
        #   "observed"   — CostModel.kernel_time's full ladder: live mean →
        #                  calibration seed → default_task_s (the default;
        #                  live observations refine the calibrated seeds)
        #   "calibrated" — the installed CalibrationProfile's seed only
        #                  (→ default_task_s when unseeded): deterministic
        #                  placement from measured numbers, immune to the
        #                  same-host jit/timing noise "observed" ingests
        #   "frozen"     — default_task_s always (== use_observed=False)
        if estimates is None:
            estimates = "observed" if use_observed else "frozen"
        if estimates not in ("observed", "calibrated", "frozen"):
            raise ValueError(f"unknown estimates mode {estimates!r}")
        self.estimates = estimates
        self._ready: Dict[int, float] = {}

    def begin(self, ctx: PlacementContext) -> None:
        self._ready = {d: 0.0 for d in range(ctx.D)}

    def _estimate(self, ctx: PlacementContext, kernel: str) -> float:
        """The compute estimate for one node, per the estimates mode."""
        if self.estimates == "frozen":
            return self.default_task_s
        if self.estimates == "calibrated":
            profile = getattr(ctx.cost, "profile", None)
            seed = profile.kernel_seed(kernel) if profile is not None else None
            return seed if seed is not None else self.default_task_s
        return ctx.cost.kernel_time(kernel, default=self.default_task_s)

    _FUNNEL = HostFunnelTransport()     # prices the fetch + re-send wire

    def _edge(self, ctx: PlacementContext, src: int, dst: int,
              nbytes: int) -> Tuple[float, str]:
        # the funnel price comes from the transport layer's own model, so
        # the two can never drift apart; edge_route folds in the per-pair
        # topology price AND the compression decision ("peer+int8" where
        # the link is thin enough for the int8 wire to win), so HEFT packs
        # hot edges intra-rack and compresses the ones it must send over
        # the spine — one comparison decides placement and routing both
        funnel = self._FUNNEL.edge_time(ctx.cost, src, dst, nbytes)
        if ctx.peer and ctx.transport is not None:
            peer_s, wire = ctx.transport.edge_route(ctx.cost, src, dst,
                                                    nbytes)
            if peer_s <= funnel:
                return peer_s, wire
        return funnel, "funnel"

    def route_edge(self, ctx: PlacementContext, src: int, dst: int,
                   nbytes: int) -> str:
        return self._edge(ctx, src, dst, nbytes)[1]

    def place(self, ctx: PlacementContext, node: TaskNode,
              ready_index: int, region_tag: str) -> int:
        est = self._estimate(ctx, node.kernel)
        cands = ctx.candidates()
        if node.device is not None and (ctx.healthy is None
                                        or node.device in cands):
            cands = [node.device]
        best, best_t = None, None
        for d in cands:
            arrive = 0.0
            for dep in node.deps:
                src = ctx.home.get(dep)
                if (src is None or src == d
                        or d in ctx.replicas.get(dep, ())):
                    continue   # already local (home or replica): free edge
                s, _ = self._edge(ctx, src, d, ctx.out_bytes.get(dep, 0))
                arrive = max(arrive, s)
            t = max(self._ready.get(d, 0.0), arrive) + est
            if best_t is None or t < best_t:
                best, best_t = d, t
        self._ready[best] = best_t
        ctx.cost.record_placement(region_tag, best, best_t, policy=self.name)
        return best


class SloPlacement(HeftPlacement):
    """Tail-latency-aware EFT placement for serving (p99, not makespan).

    HEFT minimizes *makespan*: it resets its device clocks per graph and
    greedily takes the earliest finish, which happily stacks work onto an
    already-deep queue as long as the graph's critical path doesn't grow.
    A serving fleet cares about the *tail*: one device with a standing
    backlog is exactly the p99, even when every other device is idle.

    Differences from :class:`HeftPlacement`:

    * **Backlogs persist across graphs and drain in real time.** A serving
      engine runs one small graph per decode step; per-graph clock resets
      would erase the queue state that IS the signal.  The backlog is
      *estimated seconds of queued work*, so :meth:`begin` subtracts the
      wall-clock time elapsed since the previous graph (floored at zero) —
      work placed earlier has since been executing.  Without the drain the
      backlog is cumulative-work-ever-placed, whose per-device differences
      never decay: one busy warmup would bias every later admission.
    * **Tail-first scoring.** A candidate's cost is the fleet tail that
      placement would produce — ``max(tail, finish_d)`` — so a device whose
      finish stays under the current tail is preferred over one that would
      become the new tail, even if the latter finishes this node earlier.
      Ties break by earliest finish (load balance), then capacity pressure
      (fullest present table last — a full table means the next admission
      spills a resident cache and pays refetch on every later step), then
      index (determinism).
    * **An external driver may charge/release work.** A driver with
      knowledge the placement stream lacks (a known token budget, an
      out-of-band cancellation) can adjust the backlog between ``place``
      calls via :meth:`charge` / :meth:`release`.  The serving engine
      deliberately does not: per-node charges already follow every decode
      step to its (possibly migrated) device, so lump adjustments would
      double-count.

    Edge pricing and funnel/peer routing are inherited from HEFT — the same
    :meth:`CostModel.kernel_time` / :meth:`Transport.edge_time`
    observations, so the two policies disagree only on *where*, never on
    what a wire costs.
    """

    name = "slo"

    def __init__(self, default_task_s: float = 1e-3,
                 use_observed: bool = True,
                 estimates: Optional[str] = None) -> None:
        super().__init__(default_task_s, use_observed, estimates)
        self._backlog: Dict[int, float] = {}
        self._drained_at: Optional[float] = None

    def begin(self, ctx: PlacementContext) -> None:
        # persist queue depth across graphs, draining it by the wall-clock
        # time the devices have had to work it off
        for d in range(ctx.D):
            self._backlog.setdefault(d, 0.0)
        now = time.monotonic()
        if self._drained_at is not None:
            dt = now - self._drained_at
            for d in self._backlog:
                self._backlog[d] = max(0.0, self._backlog[d] - dt)
        self._drained_at = now

    def charge(self, device: int, seconds: float) -> None:
        """Pre-charge known future work (e.g. a sequence's token budget)."""
        self._backlog[device] = self._backlog.get(device, 0.0) + seconds

    def release(self, device: int, seconds: float) -> None:
        """Return charged-but-unspent work (retirement, shed, migration)."""
        self._backlog[device] = max(0.0,
                                    self._backlog.get(device, 0.0) - seconds)

    def backlog(self, device: int) -> float:
        return self._backlog.get(device, 0.0)

    def _pressure(self, ctx: PlacementContext, d: int) -> float:
        """Resident-bytes / capacity of device ``d``'s present table
        (0 when uncapped): fuller tables spill sooner, and a spilled cache
        pays a refetch on every subsequent decode step."""
        try:
            table = ctx.pool.present[d]
        except (AttributeError, IndexError):
            return 0.0
        cap = getattr(table, "capacity_bytes", None)
        if not cap:
            return 0.0
        return table.used_bytes() / cap

    def place(self, ctx: PlacementContext, node: TaskNode,
              ready_index: int, region_tag: str) -> int:
        est = self._estimate(ctx, node.kernel)
        cands = ctx.candidates()
        if node.device is not None and (ctx.healthy is None
                                        or node.device in cands):
            cands = [node.device]
        for d in cands:
            self._backlog.setdefault(d, 0.0)
        tail = max((self._backlog[d] for d in cands), default=0.0)
        best, best_key, best_finish = None, None, None
        for d in cands:
            arrive = 0.0
            for dep in node.deps:
                src = ctx.home.get(dep)
                if (src is None or src == d
                        or d in ctx.replicas.get(dep, ())):
                    continue   # already local: free edge
                s, _ = self._edge(ctx, src, d, ctx.out_bytes.get(dep, 0))
                arrive = max(arrive, s)
            finish = max(self._backlog[d], arrive) + est
            key = (max(tail, finish), finish, self._pressure(ctx, d), d)
            if best_key is None or key < best_key:
                best, best_key, best_finish = d, key, finish
        self._backlog[best] = best_finish
        ctx.cost.record_placement(region_tag, best, best_finish,
                                  policy=self.name)
        return best


_POLICIES = {"round-robin": RoundRobin, "locality": LocalityAffinity,
             "heft": HeftPlacement, "slo": SloPlacement}


def resolve_policy(policy: Any) -> PlacementPolicy:
    """None | name | class | instance → a ready :class:`PlacementPolicy`."""
    if policy is None:
        return RoundRobin()
    if isinstance(policy, str):
        try:
            return _POLICIES[policy]()
        except KeyError:
            raise ValueError(f"unknown placement policy {policy!r}; "
                             f"one of {sorted(_POLICIES)}") from None
    if isinstance(policy, type) and issubclass(policy, PlacementPolicy):
        return policy()
    if isinstance(policy, PlacementPolicy):
        return policy
    raise TypeError(f"not a placement policy: {policy!r}")


def _value_nbytes(val: Any) -> int:
    """Bytes of a value / ShapeDtypeStruct template / pytree of either."""
    total = 0
    for l in jax.tree.leaves(
            val, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)):
        shape = getattr(l, "shape", ())
        dtype = jnp.dtype(getattr(l, "dtype", jnp.float32))
        total += int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# Resumable runs: the frontier checkpoint
# ---------------------------------------------------------------------------
@dataclass
class GraphCheckpoint:
    """Periodic frontier checkpoint making a :func:`run_graph` resumable.

    Every ``every_waves`` wave boundaries (and at the final wave) the
    completed-node frontier — each finished task's *host-reconciled* output
    value (peer-resident outputs are fetched once and cached) plus the
    completion order — is persisted atomically via
    :func:`repro.checkpoint.manager.save_pytree` under ``directory`` as
    ``step_<wave+1>``.  ``keep`` bounds retention (older steps are GC'd;
    None keeps all).  A killed coordinator then restarts with
    ``run_graph(resume_from=directory)``: completed nodes are skipped, their
    values seeded from the snapshot, and in peer mode their residency is
    rehydrated onto policy-placed devices so the remaining waves run
    exactly as they would have.

    ``halt_after=k`` raises :class:`GraphInterrupted` after the ``k``-th
    save — the deterministic "kill the coordinator at wave k" used by the
    resume tests and the CI smoke (pinned peer entries are released first,
    exactly as a real abort would).

    Task output values must be arrays or dict-pytrees of arrays (the
    manifest round trip rebuilds nested dicts; other container types would
    restore as dicts) and task names must not contain ``/``.
    """

    directory: str
    every_waves: int = 1
    keep: Optional[int] = 2
    halt_after: Optional[int] = None


class GraphInterrupted(RuntimeError):
    """A :class:`GraphCheckpoint` ``halt_after`` fired: the run stopped on
    purpose after saving; resume with ``run_graph(resume_from=...)``."""


def _checkpoint_steps(directory: str) -> List[int]:
    if not os.path.isdir(directory):
        return []
    out: List[int] = []
    for n in os.listdir(directory):
        if n.startswith("step_") and not n.endswith(".tmp"):
            try:
                out.append(int(n[5:]))
            except ValueError:
                pass
    return sorted(out)


def load_graph_checkpoint(directory: str, *, step: Optional[int] = None
                          ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Load a :class:`GraphCheckpoint` snapshot: ``(values, extra)``.

    ``values`` maps each completed task to its host output value; ``extra``
    carries the completion order (``"completed"``), the wave index and the
    graph tag.  The restore template is rebuilt from the manifest's leaf
    shapes/dtypes, so no live pytree is needed — exactly the fresh-process
    resume situation.
    """
    from ..checkpoint.manager import _np_dtype, latest_step, restore_pytree
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no graph checkpoint steps under {directory!r}")
    with open(os.path.join(directory, f"step_{step:08d}",
                           "manifest.json")) as f:
        manifest = json.load(f)
    template: Dict[str, Any] = {}
    for key, meta in manifest["leaves"].items():
        parts = key.split("/")
        node = template
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(tuple(meta["shape"]),
                                               _np_dtype(meta["dtype"]))
    tree, _, extra = restore_pytree(directory, step=step, template=template)
    return tree, dict(extra or {})


# ---------------------------------------------------------------------------
# The executor every pattern lowers into
# ---------------------------------------------------------------------------
def run_graph(ex: TargetExecutor, graph: TaskGraph, *,
              policy: Any = None, out_name: str = "out",
              nowait: bool = True, resident: bool = False,
              peer: bool = False, transport: Optional[Any] = None,
              tag: str = "graph", max_retries: int = 8,
              stragglers: Optional[Any] = None,
              checkpoint: Optional[GraphCheckpoint] = None,
              resume_from: Optional[str] = None) -> Dict[str, Any]:
    """Run a :class:`TaskGraph`: waves of ready nodes, policy-placed.

    The semantics previously private to ``wavefront_offload`` — and now
    shared by every pattern that lowers here:

    * nodes whose dependencies are satisfied dispatch as concurrent
      ``nowait`` regions, one wave at a time; host-mediated edges fetch the
      producer's value and re-send it (the paper's funnel);
    * ``resident=True`` pins a wave's *shared* plain ``to`` inputs once per
      device per wave (present-table elision for fan-outs);
    * ``peer=True`` keeps every node's ``out_name`` output resident on its
      device (``device_out``), hands consumers :class:`PeerRef`
      placeholders, and moves each cross-device edge once over the wire the
      policy routes it to — device→device via
      :meth:`TargetExecutor.propagate_resident` (tagged per consumer
      region, so a discarded region's peer records are struck with it), or
      through the host funnel when the policy prices that cheaper.

    **Failure awareness** (beyond-paper): a region that fails with
    :class:`DeviceFailure` is recovered, up to ``max_retries`` attempts per
    node, instead of aborting the graph:

    * a failed **EXEC** marks its device in the pool's
      :class:`~repro.core.device.HealthRegistry` and the node is re-placed
      by the *active policy* over the surviving candidates (a blacklisted
      device leaves the candidate set); in peer mode its resident output
      entry moves with it and the live producer map is updated, so later
      :class:`PeerRef` consumers re-resolve transparently;
    * a failed **SEND/RECV** (peer-fabric fault) reroutes the node's
      incoming edges through the host funnel — the same
      ``route_edge``-priced wire the policy could have chosen — and
      re-dispatches on the same device;
    * a failed **XFER** retries in place: resident inputs self-heal from
      their host views (:meth:`TargetExecutor._heal_locked`);
    * lost resident state is rebuilt from present-table *lineage*: when a
      producer's device-ahead entry is gone (evicted device, elastic
      shrink), its :class:`TaskNode` is **replayed** from its recorded
      dependencies and the producer map re-pointed at the new copy.

    Recovery never changes values — a recovered run is bit-identical to the
    fault-free run (chaos-tested) because every retry re-runs the same
    kernel on the same declared operands.

    The pool's membership is re-read at every wave boundary, so devices
    added by ``rescale_pool`` mid-graph become placeable on the next wave
    and removed devices leave the candidate set.

    ``policy`` (default :class:`RoundRobin`) decides device placement per
    ready node; placement affects traffic, never values.  Returns
    ``{task: host value}`` for every node.

    **Straggler hedging** (``stragglers=``): pass a detector (duck-typed on
    :class:`repro.ft.stragglers.StragglerDetector`) and the join loop polls
    in-flight regions every ``poll_s``; a region exceeding the detector's
    per-kernel threshold gets ONE hedged duplicate launched on another
    healthy candidate device (least-loaded, lowest index).  First result
    wins; the loser's cost records are struck through the speculation
    ``discard_tag`` machinery (and the winner's renamed onto the canonical
    tag), so results stay bit-identical and each task is modeled exactly
    once.  A failed primary with a hedge in flight simply waits for the
    hedge; only if both fail does normal recovery re-dispatch.  With
    ``stragglers=None`` (default) the join blocks exactly as before — zero
    overhead when the feature is off.

    **Resumable runs** (``checkpoint=`` / ``resume_from=``): see
    :class:`GraphCheckpoint`.  A resumed run must pass the same graph,
    ``tag`` and ``out_name`` as the checkpointed one.
    """
    policy = resolve_policy(policy)
    if peer and transport is None:
        from .transport import PeerTransport
        # inherit the pool's topology (ClusterRuntime installs it on the
        # cost model) so the default peer fabric prices edges per pair and
        # routes "peer+int8" where the link favors the compressed wire
        transport = PeerTransport(
            topology=getattr(ex.pool.cost, "topology", None))
    pool = ex.pool
    D = len(pool)
    ctx = PlacementContext(pool=pool, cost=pool.cost, D=D, peer=peer,
                           transport=transport,
                           healthy=pool.health.healthy(D),
                           topology=getattr(transport, "topology", None))
    policy.begin(ctx)

    # peer mode: every (device, entry-name) this run pinned — producer
    # outputs and their propagated peer copies — released in the final
    # teardown; ``producer`` maps a task to its output's CURRENT home
    # device/entry (the live map PeerRef resolution consults);
    # ``entry_owner`` is its inverse (entry name -> producing task), the
    # lineage index recovery replays from
    peer_entries: Dict[Tuple[int, str], bool] = {}
    producer: Dict[str, Tuple[int, str]] = {}
    entry_owner: Dict[str, str] = {}
    funnel_cache: Dict[str, Any] = {}   # producer task -> fetched host value
    results: Dict[str, Any] = {}

    def _refresh_membership() -> None:
        ctx.D = len(pool)
        ctx.healthy = pool.health.healthy(ctx.D)

    def _absorb() -> None:
        pool.absorb_failures()

    def _entry_live(dev: int, entry: str) -> bool:
        return (0 <= dev < len(pool)
                and pool.present[dev].get(entry) is not None)

    def _replay_producer(name: str) -> None:
        """Lineage replay: re-derive a lost resident output by re-running
        its producer node synchronously.

        The present-table entry for ``name``'s output is gone (shrunk
        device, dropped relocation) or permanently unreadable; its
        *lineage* — the producer :class:`TaskNode` and its already-settled
        dependency values in ``results`` — is not.  Replaying re-places the
        node on a healthy device, re-allocates the entry there, and
        re-points the live producer map; recursion through ``_peer_rewrite``
        covers multi-level loss, bounded by DAG depth.
        """
        t = graph.node(name)
        old = producer.get(name)
        if old is not None and old in peer_entries and _entry_live(*old):
            ex.exit_data(old[0], old[1])   # drop the dead copy's pin
        if old is not None:
            peer_entries.pop(old, None)
        ctx.replicas.pop(name, None)
        _refresh_membership()
        rtag = t.tag or f"{tag}:replay:{name}"
        dev = policy.place(ctx, t, 0, rtag)
        ctx.home[name] = dev
        ctx.replicas.setdefault(name, set()).add(dev)
        maps = t.make_maps({d: results[d] for d in t.deps})
        maps = _peer_rewrite(t, dev, maps, rtag)
        attempts = 0
        while True:
            try:
                ex.target(t.kernel, dev, maps, nowait=False, tag=rtag,
                          work=t.work)
                return
            except (DeviceFailure, KeyError):
                _absorb()
                attempts += 1
                if attempts > max_retries:
                    raise

    def _fetch_task(name: str) -> Any:
        """fetch_resident with bounded fault retry + lineage-replay rescue."""
        attempts = 0
        while True:
            dev, entry = producer[name]
            try:
                if not _entry_live(dev, entry):
                    raise KeyError(entry)
                return ex.fetch_resident(dev, entry)
            except (DeviceFailure, KeyError):
                _absorb()
                attempts += 1
                if attempts > max_retries:
                    raise
                # a fetch that keeps failing (or a vanished entry) means the
                # device copy is unrecoverable: rebuild it from lineage
                _replay_producer(name)

    def _peer_rewrite(t: TaskNode, dev: int, maps: MapSpec,
                      region_tag: str) -> MapSpec:
        new_to: Dict[str, Any] = {}
        pres: Dict[str, str] = {}
        for k, v in maps.to.items():
            if isinstance(v, PeerRef):
                # placement-independent resolution: the live producer map,
                # not the device the ref was minted with
                src_dev, entry = producer[v.task]
                if not _entry_live(src_dev, entry):
                    # producer copy lost (elastic shrink, dropped
                    # relocation): rebuild it from lineage, then re-resolve
                    if v.task in funnel_cache:
                        new_to[k] = funnel_cache[v.task]
                        continue
                    _replay_producer(v.task)
                    src_dev, entry = producer[v.task]
                if src_dev == dev or ((dev, entry) in peer_entries
                                      and _entry_live(dev, entry)):
                    pres[k] = entry
                else:
                    nb = ctx.out_bytes.get(v.task, 0)
                    route = policy.route_edge(ctx, src_dev, dev, nb)
                    if route == "funnel":
                        # the policy priced the funnel cheaper for this edge:
                        # fetch + re-map, exactly the paper's wire — ONE
                        # fetch per producer (outputs are write-once here),
                        # re-sent per consumer, like the faithful pattern
                        if v.task not in funnel_cache:
                            funnel_cache[v.task] = _fetch_task(v.task)
                        new_to[k] = funnel_cache[v.task]
                    else:
                        # per-region edge tag: a later discard_tag of this
                        # region (a speculation loser) strikes these peer
                        # records too, not only its funnel records.
                        # "peer+int8": the policy chose the block-int8 wire
                        # for this pair's link — the accounted message size
                        # shrinks to the compressed layout (the payload
                        # moves intact: modeled wire compression, so
                        # results stay bit-identical)
                        ex.propagate_resident(
                            src_dev, dev, entry, transport=transport,
                            tag=f"{region_tag}:edge",
                            compress_wire=(route == "peer+int8"))
                        peer_entries[(dev, entry)] = True
                        ctx.replicas.setdefault(v.task, set()).add(dev)
                        pres[k] = entry
            else:
                new_to[k] = v
        for k, v in {**maps.tofrom, **maps.alloc,
                     **{n: s for n, s in maps.from_.items()}}.items():
            if isinstance(v, PeerRef):
                raise TypeError(
                    f"task {t.name!r}: a PeerRef dependency may only appear "
                    f"in a to= clause (got it in {k!r})")
        if out_name not in maps.from_:
            raise ValueError(
                f"peer graph requires task {t.name!r} to declare "
                f"from_[{out_name!r}] (its resident output shape)")
        entry = f"{tag}:{t.name}"
        # re-entrant on retry: a recovered node re-placed on the SAME device
        # (or onto a device already holding a replica) reuses the live entry
        # as its output buffer instead of re-allocating
        if not _entry_live(dev, entry):
            ex.alloc_resident(dev, entry, maps.from_[out_name], tag=f"{tag}:out")
        peer_entries[(dev, entry)] = True
        producer[t.name] = (dev, entry)
        entry_owner[entry] = t.name
        ctx.out_bytes[t.name] = _value_nbytes(maps.from_[out_name])
        return MapSpec(to=new_to,
                       from_={n: s for n, s in maps.from_.items()
                              if n != out_name},
                       tofrom=maps.tofrom, alloc=maps.alloc,
                       firstprivate=maps.firstprivate,
                       use_globals=maps.use_globals,
                       present={**_alias_map(maps.present), **pres},
                       device_out={**_alias_map(maps.device_out),
                                   out_name: entry})

    def _recover(rec: Dict[str, Any], err: DeviceFailure) -> None:
        """Mutate a failed node record so it can be re-dispatched.

        EXEC faults re-place via the active policy (the failed device is
        marked in the health registry); SEND/RECV faults reroute the node's
        peer edges through the host funnel on the same device; XFER faults
        retry in place (resident inputs self-heal at the next binding).
        """
        t = rec["t"]
        # a KeyError means the region bound a replica another region's heal
        # had just dropped — recover it like an XFER fault (rebuild edges)
        op = getattr(err, "op", "XFER_TO")
        if op == "EXEC":
            fdev = err.device if err.device is not None else rec["dev"]
            pool.health.mark_failed(fdev)
            _refresh_membership()
            new_dev = policy.place(ctx, t, rec["index"], rec["tag"])
            if not (0 <= new_dev < ctx.D):
                raise ValueError(
                    f"policy {policy.name!r} re-placed {t.name!r} on "
                    f"device {new_dev} of {ctx.D}")
            ctx.load[new_dev] = ctx.load.get(new_dev, 0) + 1
            ctx.home[t.name] = new_dev
            if peer:
                entry = f"{tag}:{t.name}"
                if new_dev != rec["dev"]:
                    # abandon the unwritten output entry on the failed device
                    if (rec["dev"], entry) in peer_entries:
                        ex.exit_data(rec["dev"], entry)
                        peer_entries.pop((rec["dev"], entry), None)
                    ctx.replicas.setdefault(t.name, set()).discard(rec["dev"])
                ctx.replicas.setdefault(t.name, set()).add(new_dev)
                rec["maps"] = _peer_rewrite(t, new_dev, rec["orig_maps"],
                                            rec["tag"])
            rec["dev"] = new_dev
        elif op in ("SEND", "RECV") and peer:
            # peer fabric fault: force this node's incoming edges through
            # the host funnel (route_edge's other wire), same device
            funnel = HostFunnelTransport()
            for entry in _alias_map(rec["maps"].present).values():
                src_task = entry_owner.get(entry)
                if src_task is None:
                    continue               # user-supplied present binding
                src_dev, src_entry = producer[src_task]
                if not _entry_live(src_dev, src_entry):
                    _replay_producer(src_task)
                    src_dev, src_entry = producer[src_task]
                if src_dev != rec["dev"]:
                    ex.propagate_resident(src_dev, rec["dev"], src_entry,
                                          transport=funnel,
                                          tag=f"{rec['tag']}:edge")
                    peer_entries[(rec["dev"], src_entry)] = True
        elif peer:
            # XFER fault (or a corpse replica dropped by _heal_locked):
            # healable resident inputs re-send from their host views at the
            # next binding; an edge whose replica was dropped must be
            # re-propagated, so rebuild the node's maps before re-dispatch
            rec["maps"] = _peer_rewrite(t, rec["dev"], rec["orig_maps"],
                                        rec["tag"])
        # XFER_TO/XFER_FROM outside peer mode: plain retry — _heal_locked
        # re-sends damaged resident inputs at the next binding

    def _run_recovering(rec: Dict[str, Any]) -> Dict[str, Any]:
        """Synchronous dispatch with the same recovery loop (nowait=False)."""
        while True:
            try:
                return ex.target(rec["t"].kernel, rec["dev"], rec["maps"],
                                 nowait=False, tag=rec["tag"],
                                 work=rec["t"].work)
            except (DeviceFailure, KeyError) as err:
                _absorb()
                while True:
                    rec["attempts"] += 1
                    if rec["attempts"] > max_retries:
                        raise err
                    try:
                        _recover(rec, err)
                        break
                    except (DeviceFailure, KeyError) as err2:
                        _absorb()
                        err = err2

    def _launch_hedge(rec: Dict[str, Any]) -> None:
        """Race a duplicate of a straggling region on another device.

        The hedge's tag uses a ``~`` separator (``<tag>~hedge<n>``):
        :func:`~repro.core.costmodel._tag_matches` treats only ``:`` and
        ``[`` as child separators, so ``discard_tag(rec['tag'])`` strikes
        the primary WITHOUT touching the hedge's records and vice versa —
        the race's loser can always be struck cleanly.
        """
        t = rec["t"]
        cands = [d for d in ctx.candidates() if d != rec["dev"]]
        if not cands:
            return
        hdev = min(cands, key=lambda d: (ctx.load.get(d, 0), d))
        rec["hedge_count"] = rec.get("hedge_count", 0) + 1
        htag = f"{rec['tag']}~hedge{rec['hedge_count']}"
        prev = producer.get(t.name) if peer else None
        elapsed = time.monotonic() - rec["start"]
        entry = f"{tag}:{t.name}"
        try:
            hmaps = (_peer_rewrite(t, hdev, rec["orig_maps"], htag)
                     if peer else rec["orig_maps"])
            # no ``work``: the task's cells count once, on its primary
            hfut = ex.target(t.kernel, hdev, hmaps, nowait=True, tag=htag)
        except (DeviceFailure, KeyError):
            # the hedge could not even launch: undo its peer bookkeeping
            # and keep racing the primary alone
            _absorb()
            if peer:
                if prev is not None:
                    producer[t.name] = prev
                if ((prev is None or prev[0] != hdev)
                        and (hdev, entry) in peer_entries):
                    ex.exit_data(hdev, entry)
                    peer_entries.pop((hdev, entry), None)
            return
        ctx.load[hdev] = ctx.load.get(hdev, 0) + 1
        hrec = stragglers.note_launch(
            task=t.name, kernel=t.kernel, primary_device=rec["dev"],
            hedge_device=hdev, elapsed_s=elapsed,
            threshold_s=stragglers.threshold(t.kernel) or 0.0)
        rec["hedge"] = {"fut": hfut, "tag": htag, "dev": hdev,
                        "prev_producer": prev, "record": hrec}

    def _drop_hedge(rec: Dict[str, Any], outcome: str) -> None:
        """Strike a settled, losing hedge; restore the primary's state."""
        h = rec["hedge"]
        t = rec["t"]
        entry = f"{tag}:{t.name}"
        _absorb()
        pool.cost.discard_tag(h["tag"])
        if peer:
            if h["prev_producer"] is not None:
                producer[t.name] = h["prev_producer"]
            keep_dev = producer.get(t.name, (None,))[0]
            if h["dev"] != keep_dev and (h["dev"], entry) in peer_entries:
                ex.exit_data(h["dev"], entry)
                peer_entries.pop((h["dev"], entry), None)
                ctx.replicas.setdefault(t.name, set()).discard(h["dev"])
        stragglers.note_winner(h["record"], outcome)
        rec["hedge"] = None

    def _promote_hedge(rec: Dict[str, Any]) -> None:
        """The hedge won the race: canonicalize it, strike the primary."""
        h = rec["hedge"]
        t = rec["t"]
        entry = f"{tag}:{t.name}"
        _absorb()
        # order matters: strike the loser FIRST, then rename the winner's
        # records onto the canonical tag (renaming first would hand the
        # winner's records to the discard)
        pool.cost.discard_tag(rec["tag"])
        pool.cost.rename_tag(h["tag"], rec["tag"])
        if peer:
            producer[t.name] = (h["dev"], entry)
            pdev = rec["dev"]
            if pdev != h["dev"] and (pdev, entry) in peer_entries:
                ex.exit_data(pdev, entry)
                peer_entries.pop((pdev, entry), None)
                ctx.replicas.setdefault(t.name, set()).discard(pdev)
            ctx.replicas.setdefault(t.name, set()).add(h["dev"])
            ctx.home[t.name] = h["dev"]
        stragglers.note_winner(h["record"], "hedge")
        rec["hedge"] = None

    def _settle_hedges(records: List[Dict[str, Any]]) -> None:
        """Decide every still-open race once both copies have settled.

        A winner is *taken* the moment it lands, but the loser's records can
        only be struck after the loser settles (its cost records land at
        completion) — so resolution is deferred to here, after the join.
        """
        for rec in records:
            h = rec.get("hedge")
            if h is None:
                continue
            _cf.wait([rec["fut"]._fut, h["fut"]._fut])
            if rec.get("winner") == "hedge":
                _promote_hedge(rec)
            else:
                _drop_hedge(rec, "primary")

    def _join_recovering(records: List[Dict[str, Any]]) -> None:
        """Join a wave's ``nowait`` regions, recovering failed ones.

        Like :meth:`TargetExecutor.drain` this returns only once EVERY
        region (including re-dispatched ones and hedges) has settled, so
        pin releases after it can never pull a buffer from under a running
        region.  Outcomes land in each record's ``out``.

        With a straggler detector the wait becomes a poll: each pass checks
        in-flight primaries against the detector's threshold and races a
        hedged duplicate when one trips.  The primary is preferred on ties
        (deterministic); a failed primary with a live hedge waits for the
        hedge instead of burning a recovery attempt.
        """
        all_futs: List[TargetFuture] = [r["fut"] for r in records]
        pending = list(records)
        try:
            while pending:
                waitset = [r["fut"]._fut for r in pending]
                waitset += [r["hedge"]["fut"]._fut for r in pending
                            if r.get("hedge")]
                if stragglers is None:
                    _cf.wait(waitset)
                else:
                    _cf.wait(waitset, timeout=stragglers.poll_s,
                             return_when=_cf.FIRST_COMPLETED)
                nxt: List[Dict[str, Any]] = []
                for rec in pending:
                    pf = rec["fut"]._fut
                    h = rec.get("hedge")
                    if pf.done() and pf.exception() is None:
                        rec["out"] = pf.result()
                        if h is not None:
                            rec["winner"] = "primary"
                        continue
                    if h is not None and h["fut"]._fut.done():
                        herr = h["fut"]._fut.exception()
                        if herr is None:
                            rec["out"] = h["fut"]._fut.result()
                            rec["winner"] = "hedge"
                            continue
                        if not isinstance(herr, (DeviceFailure, KeyError)):
                            raise herr
                        _drop_hedge(rec, "failed")
                        h = None
                    if pf.done():
                        err = pf.exception()
                        if not isinstance(err, (DeviceFailure, KeyError)):
                            raise err
                        if h is not None:
                            # the hedge is still racing: let it decide the
                            # node before spending a recovery attempt
                            nxt.append(rec)
                            continue
                        _absorb()
                        while True:
                            rec["attempts"] += 1
                            if rec["attempts"] > max_retries:
                                raise err
                            try:
                                _recover(rec, err)
                                break
                            except (DeviceFailure, KeyError) as err2:
                                _absorb()
                                err = err2
                        rec["start"] = time.monotonic()
                        rec["fut"] = ex.target(rec["t"].kernel, rec["dev"],
                                               rec["maps"], nowait=True,
                                               tag=rec["tag"],
                                               work=rec["t"].work)
                        all_futs.append(rec["fut"])
                        nxt.append(rec)
                        continue
                    # primary still in flight: maybe race a duplicate
                    if (stragglers is not None and h is None
                            and rec.get("hedge_count", 0) < 1
                            and stragglers.should_hedge(
                                rec["t"].kernel,
                                time.monotonic() - rec["start"])):
                        _launch_hedge(rec)
                        if rec.get("hedge") is not None:
                            all_futs.append(rec["hedge"]["fut"])
                    nxt.append(rec)
                pending = nxt
            if stragglers is not None:
                _settle_hedges(records)
        finally:
            # error path: settle everything still in flight before the
            # caller's teardown releases pins
            live = [f._fut for f in all_futs if not f._fut.done()]
            if live:
                _cf.wait(live)
            ex.retire(all_futs)

    # -- resumable runs: frontier snapshot + rehydration ----------------------
    completed: set = set()
    host_snap: Dict[str, Any] = {}     # task -> host value (checkpoint cache)
    ckpt_saves = [0]

    def _save_checkpoint(wave_idx: int) -> None:
        """Persist the completed-node frontier after ``wave_idx``.

        Peer-resident outputs are host-reconciled (fetched once, cached
        incrementally across saves), so the snapshot is self-contained: a
        fresh process restores values without any live device state.
        """
        from ..checkpoint.manager import save_pytree
        for name in results:
            if name not in host_snap:
                host_snap[name] = _fetch_task(name) if peer else results[name]
        snap = {n: host_snap[n] for n in results}
        save_pytree(checkpoint.directory, wave_idx + 1, snap,
                    extra={"completed": list(results), "wave": wave_idx,
                           "graph_tag": tag, "out_name": out_name})
        ckpt_saves[0] += 1
        if checkpoint.keep is not None:
            for s in _checkpoint_steps(checkpoint.directory)[:-checkpoint.keep]:
                shutil.rmtree(os.path.join(checkpoint.directory,
                                           f"step_{s:08d}"),
                              ignore_errors=True)
        if (checkpoint.halt_after is not None
                and ckpt_saves[0] >= checkpoint.halt_after):
            raise GraphInterrupted(
                f"run_graph halted on purpose after save {ckpt_saves[0]} "
                f"(wave {wave_idx}); resume from {checkpoint.directory!r}")

    if resume_from is not None:
        snap, ck_extra = load_graph_checkpoint(resume_from)
        order = [n for n in ck_extra.get("completed", sorted(snap))
                 if n in snap]
        for idx, name in enumerate(order):
            if name not in graph.nodes:
                raise ValueError(
                    f"checkpointed task {name!r} is not in this graph — "
                    f"resume requires the DAG that was checkpointed")
            value = snap[name]
            completed.add(name)
            host_snap[name] = value
            ctx.out_bytes[name] = _value_nbytes(value)
            if peer:
                # rehydrate residency: the restored value re-enters a
                # device data environment on a policy-placed device, so the
                # remaining waves bind it exactly like a live producer's
                # output (``**{entry: ...}`` — entry names contain ':')
                t = graph.node(name)
                rtag = t.tag or f"{tag}:resume:{name}"
                dev = policy.place(ctx, t, idx, rtag)
                if not (0 <= dev < ctx.D):
                    raise ValueError(
                        f"policy {policy.name!r} re-placed restored "
                        f"{name!r} on device {dev} of {ctx.D}")
                entry = f"{tag}:{name}"
                ex.enter_data(dev, f"{tag}:resume", **{entry: value})
                peer_entries[(dev, entry)] = True
                producer[name] = (dev, entry)
                entry_owner[entry] = name
                ctx.home[name] = dev
                ctx.replicas.setdefault(name, set()).add(dev)
                results[name] = PeerRef(name, entry, dev)
            else:
                results[name] = value

    def _run_wave(wave_idx: int, wave: List[str]) -> None:
        """One wave: plan, issue and join its regions; pins released at
        its end."""
        ready = [graph.node(n) for n in wave if n not in completed]
        ctx.wave = wave_idx
        # wave boundary: advance blacklist probation (a clean wave accrues
        # rejoin credit) and re-read pool membership and device health, so a
        # device joined mid-graph is placeable from the next wave on and a
        # removed/blacklisted one leaves the candidate set
        pool.health.tick_wave()
        _refresh_membership()
        D = ctx.D
        ctx.load = {d: 0 for d in range(D)}
        entered: List[Tuple[int, str]] = []
        futs: List[TargetFuture] = []
        records: List[Dict[str, Any]] = []
        joined = False
        try:
            with span(spans.WAVE_PLAN):
                plans: List[Dict[str, Any]] = []
                for j, t in enumerate(ready):
                    region_tag = t.tag or f"{tag}:w{wave_idx}:{t.name}"
                    dev = policy.place(ctx, t, j, region_tag)
                    if not (0 <= dev < D):
                        raise ValueError(
                            f"policy {policy.name!r} placed {t.name!r} on "
                            f"device {dev} of {D}")
                    ctx.load[dev] = ctx.load.get(dev, 0) + 1
                    ctx.home[t.name] = dev
                    ctx.replicas.setdefault(t.name, set()).add(dev)
                    orig_maps = t.make_maps({d: results[d] for d in t.deps})
                    maps = (_peer_rewrite(t, dev, orig_maps, region_tag)
                            if peer else orig_maps)
                    plans.append({"t": t, "dev": dev, "tag": region_tag,
                                  "maps": maps, "orig_maps": orig_maps,
                                  "index": j, "attempts": 0, "out": None})
                if resident:
                    # pin only values genuinely shared: a (device, name) whose
                    # plain to/tofrom value is identical across >=2 of the wave's
                    # tasks.  Pinning per-task-varying values would gain nothing
                    # and each refresh could race an in-flight sibling region out
                    # of its elision (value-correct either way, but the byte
                    # savings would depend on thread scheduling).
                    usage: Dict[Tuple[int, str], List[Tuple[Tuple[int, ...], Any]]] = {}
                    for p in plans:
                        dev, maps = p["dev"], p["maps"]
                        # to-maps only: tofrom buffers are written back per task,
                        # and two regions sharing one pinned output handle would
                        # fetch each other's results
                        for n, v in maps.to.items():
                            leaves, _ = _flatten_map_value(v)
                            if any(isinstance(l, Section) for l in leaves):
                                continue   # sections differ per task: not pinnable
                            usage.setdefault((dev, n), []).append(
                                (tuple(id(l) for l in leaves), v))
                    for (dev, n), uses in usage.items():
                        if len(uses) < 2 or len({k for k, _ in uses}) != 1:
                            continue       # unique or conflicting values: no pin
                        try:
                            ex.enter_data(dev, f"{tag}:w{wave_idx}", **{n: uses[0][1]})
                            entered.append((dev, n))
                        except ValueError:
                            pass           # shape changed under this name: skip pin
            with span(spans.WAVE_ISSUE):
                for p in plans:
                    t = p["t"]
                    if nowait:
                        p["start"] = time.monotonic()
                        p["fut"] = ex.target(t.kernel, p["dev"], p["maps"],
                                             nowait=True, tag=p["tag"],
                                             work=t.work)
                        futs.append(p["fut"])
                        records.append(p)
                    else:
                        out = _run_recovering(p)
                        results[t.name] = (PeerRef(t.name, producer[t.name][1],
                                                   producer[t.name][0])
                                           if peer else out[out_name])
                        if not peer:
                            ctx.out_bytes[t.name] = _value_nbytes(results[t.name])
            if records:
                # the join waits for EVERY region to settle (even past a
                # failure, even across re-dispatches), so the pin release
                # below can never pull a buffer out from under a
                # still-running region
                joined = True
                with span(spans.WAVE_JOIN):
                    _join_recovering(records)
                for p in records:
                    t = p["t"]
                    results[t.name] = (PeerRef(t.name, producer[t.name][1],
                                               producer[t.name][0])
                                       if peer else p["out"][out_name])
                    if not peer:
                        ctx.out_bytes[t.name] = _value_nbytes(results[t.name])
            if checkpoint is not None and ready:
                waves_done = wave_idx + 1
                if (waves_done % max(1, checkpoint.every_waves) == 0
                        or wave_idx == len(waves) - 1):
                    # inside the try on purpose: a halt_after raise takes
                    # the teardown path below, releasing pinned peer
                    # entries exactly as a real coordinator death would
                    _save_checkpoint(wave_idx)
        except BaseException:
            if peer:
                # failed run: nothing will fetch the resident outputs, so
                # release every pinned entry.  Safe even before the finally
                # below joins a mid-dispatch wave: in-flight regions hold
                # their own present-table references, so an entry is only
                # freed once its last region has released it.
                for dev, n in peer_entries:
                    if dev < len(pool):    # elastic shrink may have removed it
                        ex.exit_data(dev, n)
            raise
        finally:
            if futs and not joined:
                # a mid-dispatch failure (a later task's make_maps or launch
                # raised): the already-launched regions must still be joined
                # and retired before their pins are released
                try:
                    ex.drain(futs)
                except BaseException:
                    pass               # the dispatch error propagates
            for dev, n in entered:      # wave boundary: release pins
                if dev < len(pool):
                    ex.exit_data(dev, n)

    # the topological decomposition is the graph's own (one wave drains
    # fully before the next is planned, so ready == waves()); cycles and
    # missing deps surface here, before anything is dispatched
    waves = graph.waves()
    try:
        for wave_idx, wave in enumerate(waves):
            with span(spans.WAVE, wave=wave_idx):
                _run_wave(wave_idx, wave)
        if peer:
            # materialize the host view — one fetch per task output, exactly
            # what the host-mediated run's from_ maps moved — then release
            # every entry this run pinned (outputs and propagated peer copies)
            try:
                for name in list(producer):
                    results[name] = _fetch_task(name)
            finally:
                for dev, n in peer_entries:
                    if dev < len(pool):
                        ex.exit_data(dev, n)
        return results
    finally:
        # _replay_producer and _peer_rewrite call each other, so their
        # closures form a reference cycle that holds ``results`` and every
        # array of the run until the cyclic collector runs (device memory
        # of many finished runs at once); unbinding one breaks the cycle
        _peer_rewrite = None
