"""NodeDevice / DevicePool: cluster nodes as offload devices (paper §4).

An ``mpinode`` device in the paper is "simply a computer with MPI installed",
listed in a configuration file; listing a node with a multiplier ``D`` starts
``D`` devices on it.  Here a :class:`NodeDevice` wraps either

* a real ``jax.Device``,
* a mesh *sub-slice* (a set of chips acting as one device — the natural
  granularity on a TPU pod), or
* a *virtual* share of one device (the paper's ``D``-per-node feature; also how
  we simulate an N-device cluster on this CPU-only container).

Each device owns a :class:`MediaryStore`; the host side owns one
:class:`HostMirror` per device plus a per-device mutex (paper §4.2: "we lock a
mutex dedicated to the device we want to use").  Every transfer is accounted
in a :class:`CostModel`.
"""
from __future__ import annotations

import collections
import concurrent.futures as _cf
import inspect
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .costmodel import CostModel, LinkModel, PAPER_ETHERNET
from .kernel_table import GLOBAL_KERNEL_TABLE, KernelTable
from .mediary import HostMirror, MediaryStore, PresentTable


# ---------------------------------------------------------------------------
# Command stream (paper §4.1: the four command types + STOP)
# ---------------------------------------------------------------------------
#: Pseudo-handle every ALLOC/FREE writes: chains them in issue order so the
#: device-side first-fit allocator sees the exact sequence the host mirror
#: predicted, even though unrelated transfers/EXECs may reorder around them.
SLOT_STREAM = -1


def host_array(value: Any) -> Any:
    """``value`` as an array without placing it on any device.

    A ``jax.Array`` passes through where it lives; anything else becomes a
    NumPy array of the dtype JAX would give it.  The pool places host values
    on their target device directly, so a transfer to device ``d`` never
    stages through the process's default device first.
    """
    if isinstance(value, jax.Array):
        return value
    a = np.asarray(value)
    if a.dtype.kind in "OSUMm":         # what jnp.asarray refuses, refused
        raise TypeError(f"value of dtype {a.dtype} is not a valid JAX "
                        f"array type")
    return a.astype(jax.dtypes.canonicalize_dtype(a.dtype), copy=False)


@dataclass(frozen=True)
class Command:
    op: str                 # ALLOC | FREE | XFER_TO | XFER_FROM | EXEC |
                            # SEND | RECV | STOP
    device: int
    handle: Optional[int] = None
    nbytes: int = 0
    kernel_index: Optional[int] = None
    tag: str = ""
    # dependency-aware stream: the buffer handles this command reads/writes.
    # Per-handle issue order (producer XFER/EXEC before consumer
    # EXEC/XFER_FROM, consumer before the *next* producer) is what the
    # device worker enforces instead of whole-queue serialization.
    reads: Tuple[int, ...] = ()
    writes: Tuple[int, ...] = ()
    # SEND/RECV only: the other endpoint of the device↔device transfer
    peer: Optional[int] = None


class NodeDevice:
    """One offload device: buffer store + kernel executor on its sharding."""

    def __init__(self, index: int, *, jax_device: Optional[jax.Device] = None,
                 sharding: Optional[jax.sharding.Sharding] = None,
                 hostname: str = "localhost",
                 capacity_bytes: Optional[int] = None) -> None:
        self.index = index
        self.hostname = hostname
        self.jax_device = jax_device
        self.sharding = sharding
        self.store = MediaryStore(sharding=sharding)
        # where this device's buffers live: its sharding, else its jax device
        self.placement = sharding if sharding is not None else jax_device
        self.stopped = False
        # resident-memory budget for this device's present table (None =
        # unbounded); enforced by the executor's LRU spill path, not here
        self.capacity_bytes = capacity_bytes
        self._jit_cache: Dict[int, Callable] = {}

    def _place(self, value: Any) -> jax.Array:
        """Put ``value`` on this device; an array already there is aliased
        (``device_put`` to its own placement copies nothing)."""
        if self.placement is None:
            return value
        return jax.device_put(value, self.placement)

    # -- the device-side command loop (paper §4.1) --------------------------
    def execute(self, cmd: Command, table: KernelTable,
                payload: Optional[Dict[str, Any]] = None):
        if self.stopped:
            raise RuntimeError(f"device {self.index} is stopped")
        if cmd.op == "ALLOC":
            handle = self.store.alloc(payload["shape"], payload["dtype"])
            assert handle == cmd.handle, (
                f"mediary desync: device allocated slot {handle}, host "
                f"reserved {cmd.handle}")
            return handle
        if cmd.op == "FREE":
            self.store.free(cmd.handle)
            return None
        if cmd.op == "XFER_TO":
            self.store.write(cmd.handle, self._place(payload["value"]),
                             section=payload.get("section"))
            return None
        if cmd.op == "XFER_FROM":
            return self.store.read(cmd.handle, section=payload.get("section"))
        if cmd.op == "SEND":
            # peer rendezvous, source side: the command's future carries the
            # buffer to the peer's RECV (the wire of the modeled link)
            return self.store.read(cmd.handle)
        if cmd.op == "RECV":
            # peer rendezvous, sink side: the matching SEND has settled (the
            # stream gates RECV on it — a cross-device dependency edge), so
            # this never blocks the worker; a failed SEND re-raises here
            value = payload["source"].result()
            self.store.write(cmd.handle, self._place(value))
            return None
        if cmd.op == "EXEC":
            entry = table.lookup(cmd.kernel_index)
            fn = self._jit_cache.get(cmd.kernel_index)
            if fn is None:
                fn = jax.jit(entry.fn, static_argnames=payload.get("static_argnames", ()))
                self._jit_cache[cmd.kernel_index] = fn
            # buffers: name -> handle, or name -> [handles] for pytree-valued
            # maps; the treedef travels in the EXEC message (paper §4.2: "the
            # host creates a struct in which it places the mediary address
            # for each variable ... and sends the struct to the device").
            trees = payload.get("trees", {})
            kwargs = {}
            for name, h in payload["buffers"].items():
                if isinstance(h, (list, tuple)):
                    leaves = [self.store.device_address(x) for x in h]
                    kwargs[name] = jax.tree.unflatten(trees[name], leaves)
                else:
                    kwargs[name] = self.store.device_address(h)
            kwargs.update(payload.get("firstprivate", {}))
            # OpenMP kernels mutate mapped buffers in place; JAX kernels are
            # functional, so a kernel only *receives* the mapped names it
            # declares as parameters (a pure-``from`` output buffer need not
            # be an input) and *returns* the from/tofrom values.
            params = inspect.signature(entry.fn).parameters
            if not any(p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()):
                kwargs = {k: v for k, v in kwargs.items() if k in params}
            return fn(**kwargs)
        if cmd.op == "STOP":
            self.stopped = True
            return None
        raise ValueError(f"unknown command {cmd.op}")


class DeviceStoppedError(RuntimeError):
    """Command issued to a device whose queue has been closed by stop_all."""


class DeviceFailure(RuntimeError):
    """A device-side command failed (injected or real).

    Carries enough context for graph-level recovery: ``op`` names the failed
    command (EXEC / SEND / RECV / XFER_TO / XFER_FROM) and ``device`` the
    device that raised.  Lives in ``core`` so the runtime can catch it
    without importing ``ft``; ``repro.ft`` re-exports it.
    """

    def __init__(self, message: str, *, op: str = "EXEC",
                 device: Optional[int] = None,
                 kernel_index: Optional[int] = None) -> None:
        super().__init__(message)
        self.op = op
        self.device = device
        self.kernel_index = kernel_index


class StragglerTimeout(DeviceFailure):
    """A command missed its deadline — a gray failure, not a crash.

    Subclasses :class:`DeviceFailure` so every existing recovery path
    (re-place, reroute, heal) treats a blown deadline as just another
    recoverable fault.  The wedged command keeps running on its worker and
    settles harmlessly later; by then the host has already recovered
    elsewhere and :meth:`DevicePool.absorb_failures` clears whatever the
    abandoned copy stashed.
    """


class HealthRegistry:
    """Shared device-health bookkeeping for failure-aware scheduling.

    Placement policies consult :meth:`healthy`; recovery paths call
    :meth:`mark_failed` when a device raises :class:`DeviceFailure`.  A
    device is blacklisted once its failure count reaches ``max_failures`` —
    one transient fault does not remove a device, a repeat offender does.
    When *every* device is blacklisted, :meth:`healthy` falls back to the
    full set (availability beats avoidance: with p<1 injection a retry on a
    flaky device still converges).

    ``probation_waves=N`` enables blacklist *probation*: the graph executor
    calls :meth:`tick_wave` at every wave boundary, and a blacklisted device
    that stays clean for ``N`` consecutive waves rejoins the candidate set
    with one strike left (``max_failures - 1``) — a transiently-slow node
    comes back, a chronic one re-blacklists on its next fault.  Rejoins are
    capped at ``max_rejoins`` per device; past the cap the device stays out
    for the rest of the run.  Default ``None`` keeps the PR-6 behavior
    (blacklisted for the whole run).
    """

    def __init__(self, max_failures: int = 2, *,
                 probation_waves: Optional[int] = None,
                 max_rejoins: int = 2) -> None:
        self.max_failures = max_failures
        self.probation_waves = probation_waves
        self.max_rejoins = max_rejoins
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._blacklist: set = set()
        self._clean: Dict[int, int] = {}     # consecutive clean waves
        self._rejoins: Dict[int, int] = {}   # probation rejoins so far
        self._dirty: set = set()             # failed since last tick_wave

    def mark_failed(self, device: Optional[int]) -> None:
        if device is None:
            return
        with self._lock:
            self._counts[device] = self._counts.get(device, 0) + 1
            self._dirty.add(device)
            self._clean.pop(device, None)
            if self._counts[device] >= self.max_failures:
                self._blacklist.add(device)

    def mark_healthy(self, device: int) -> None:
        """Forget a device's failure history (rejoin after repair)."""
        with self._lock:
            self._counts.pop(device, None)
            self._blacklist.discard(device)
            self._clean.pop(device, None)
            self._rejoins.pop(device, None)
            self._dirty.discard(device)

    def tick_wave(self) -> List[int]:
        """Advance probation at a wave boundary; returns devices rejoined.

        A blacklisted device with no failures since the last tick accrues
        one clean wave; at ``probation_waves`` it rejoins with its count
        reset to ``max_failures - 1`` (one strike from re-blacklisting),
        unless it has already used its ``max_rejoins`` budget.
        """
        rejoined: List[int] = []
        with self._lock:
            dirty, self._dirty = self._dirty, set()
            if self.probation_waves is None:
                return rejoined
            for d in sorted(self._blacklist):
                if d in dirty:
                    self._clean[d] = 0
                    continue
                self._clean[d] = self._clean.get(d, 0) + 1
                if self._clean[d] < self.probation_waves:
                    continue
                if self._rejoins.get(d, 0) >= self.max_rejoins:
                    continue                 # chronic offender: stays out
                self._rejoins[d] = self._rejoins.get(d, 0) + 1
                self._blacklist.discard(d)
                self._clean.pop(d, None)
                self._counts[d] = self.max_failures - 1
                rejoined.append(d)
        return rejoined

    def failures(self, device: int) -> int:
        with self._lock:
            return self._counts.get(device, 0)

    @property
    def blacklist(self) -> set:
        with self._lock:
            return set(self._blacklist)

    def is_healthy(self, device: int) -> bool:
        with self._lock:
            return device not in self._blacklist

    def healthy(self, n: int) -> List[int]:
        """Non-blacklisted device indices in ``range(n)`` (all if none are)."""
        with self._lock:
            out = [d for d in range(n) if d not in self._blacklist]
        return out if out else list(range(n))


class _WorkItem:
    """One enqueued command: a closure the device worker runs in order."""

    __slots__ = ("fn", "future")

    def __init__(self, fn: Callable[[], Any], future: "_cf.Future") -> None:
        self.fn = fn
        self.future = future


class StreamTicket:
    """A registered *reader* of device-stream handles.

    Opened under the data-environment lock when a region matches a present
    entry, closed once the region's EXEC has consumed the matched content.
    While open, any later command that writes those handles (a concurrent
    region's refresh, a writeback) is held back — write-after-read ordering
    across the match→EXEC window, which is what makes ``nowait`` regions
    safe to share present-table entries.

    ``deps`` are the last-writer futures of the handles at open time: the
    consuming EXEC must run after them (read-after-write ordering).
    """

    __slots__ = ("deps", "_fut")

    def __init__(self, deps: Sequence["_cf.Future"], fut: "_cf.Future") -> None:
        self.deps: Tuple["_cf.Future", ...] = tuple(deps)
        self._fut = fut

    def close(self) -> None:
        """Release the reader registration (idempotent)."""
        if not self._fut.done():
            self._fut.set_result(None)


class DevicePool:
    """Host view of all devices (paper: the parsed configuration file).

    ``DevicePool.from_config(["node0 2", "node1"])`` yields 3 devices, the
    first two being virtual shares of node0 — the paper's multiplier feature.
    Every hostname resolves to the process's first JAX device, so a config
    pool is virtual shares of one chip; :meth:`from_mesh_slices` gives one
    device per chip (or per mesh sub-slice) instead.

    Commands flow through a **dependency-aware per-device stream** drained by
    one worker thread per device (the paper's device-side command loop made
    asynchronous).  Each command names the buffer handles it reads and
    writes; a command becomes runnable once the last writer of every handle
    it touches — and, for writers, every registered reader — has settled.
    Only that per-handle order is enforced: issuing a transfer returns as
    soon as the command is registered, and commands on disjoint handles may
    run in either order, so ``nowait`` regions can safely interleave their
    command batches on one device (they serialize exactly where their data
    dependencies demand).  ALLOC/FREE additionally write the ``SLOT_STREAM``
    pseudo-handle, chaining them in issue order so the device's first-fit
    allocator replays the exact sequence the host mirror predicted under
    ``locks[d]`` — the handle-agreement property survives reordering.
    Ops that produce a value (EXEC, XFER_FROM) block on their command's
    future.  ``stream_traces[d]`` records *execution* order (``trace`` keeps
    issue order) so tests can assert producer-before-consumer.
    """

    def __init__(self, devices: Sequence[NodeDevice], *,
                 table: Optional[KernelTable] = None,
                 link: LinkModel = PAPER_ETHERNET,
                 capacity_bytes: Optional[int] = None,
                 deadline_s: Optional[float] = None) -> None:
        self.devices = list(devices)
        self.table = table or GLOBAL_KERNEL_TABLE
        self.cost = CostModel(link)
        # pool-wide default budget for devices joining later (add_device)
        self._default_capacity = capacity_bytes
        # per-command deadline on the value-producing ops (EXEC, XFER_FROM):
        # a blown deadline raises StragglerTimeout instead of waiting forever
        # on a wedged worker.  None (default) = wait indefinitely.
        self.deadline_s = deadline_s
        # observability: blown deadlines by op, and stashed DeviceFailures
        # that recovery cleared (both guarded by _trace_lock)
        self.straggler_timeouts: Dict[str, int] = {}
        self.failures_absorbed = 0
        # shared failure bookkeeping consulted by placement policies
        self.health = HealthRegistry()
        self.mirrors = [HostMirror() for _ in self.devices]
        # RLocks: _submit re-acquires the issue lock the issue methods hold
        self.locks = [threading.RLock() for _ in self.devices]
        # per-device capacity wins over the pool-wide default
        self.present = [PresentTable(capacity_bytes=(
            d.capacity_bytes if d.capacity_bytes is not None
            else capacity_bytes)) for d in self.devices]
        self.env_locks = [threading.RLock() for _ in self.devices]
        self.trace: List[Command] = []
        # name -> {device: handle}; first-fit may place a global at different
        # slots across devices when other buffers are already pinned on some
        self.globals: Dict[str, Dict[int, int]] = {}
        # name -> host value, retained so devices joining later (add_device)
        # can replay the install sequence
        self._global_values: Dict[str, Any] = {}
        self._trace_lock = threading.Lock()
        self._queues: List["queue.SimpleQueue[Optional[_WorkItem]]"] = [
            queue.SimpleQueue() for _ in self.devices]
        self._stopped = [False for _ in self.devices]
        self._async_errors: List[Optional[BaseException]] = [None] * len(self.devices)
        # dependency-stream state, all guarded by locks[d]:
        self._last_write: List[Dict[int, "_cf.Future"]] = [
            {} for _ in self.devices]       # handle -> last writer's future
        self._readers: List[Dict[int, List["_cf.Future"]]] = [
            {} for _ in self.devices]       # handle -> readers since last write
        self._outstanding: List[List["_cf.Future"]] = [[] for _ in self.devices]
        # ring-buffered (unlike the issue-order `trace`): execution order is
        # a debugging/testing aid and must not grow with run length
        self.stream_traces: List["collections.deque[Command]"] = [
            collections.deque(maxlen=4096) for _ in self.devices]
        self._workers = []
        for i in range(len(self.devices)):
            t = threading.Thread(target=self._worker, args=(i,),
                                 name=f"omp-dev{i}", daemon=True)
            t.start()
            self._workers.append(t)

    # -- the per-device command-queue worker ---------------------------------
    def _worker(self, device: int) -> None:
        q = self._queues[device]
        while True:
            item = q.get()
            if item is None:                 # sentinel: queue closed
                return
            try:
                item.future.set_result(item.fn())
            except BaseException as e:       # propagate to the issuer
                item.future.set_exception(e)

    def _stream_deps(self, device: int, fut: "_cf.Future",
                     reads: Sequence[int], writes: Sequence[int],
                     extra_deps: Sequence["_cf.Future"]) -> List["_cf.Future"]:
        """Collect this command's dependencies and register it; under locks[d].

        Read-after-write: wait for the last writer of every handle touched.
        Write-after-read: a writer also waits for every reader registered
        since that last write (including open :class:`StreamTicket`\\ s).
        """
        lw, rd = self._last_write[device], self._readers[device]
        deps: Dict[int, "_cf.Future"] = {}
        for h in (*reads, *writes):
            f = lw.get(h)
            if f is not None and not f.done():
                deps[id(f)] = f
        for h in writes:
            for f in rd.get(h, ()):
                if not f.done():
                    deps[id(f)] = f
        for f in extra_deps:
            if f is not None and not f.done():
                deps[id(f)] = f
        for h in writes:
            lw[h] = fut
            rd[h] = []
        for h in reads:
            self._note_reader(rd, h, fut)
        return list(deps.values())

    @staticmethod
    def _note_reader(rd: Dict[int, List["_cf.Future"]], h: int,
                     fut: "_cf.Future") -> None:
        """Register a reader of ``h``, pruning settled ones: a handle read
        forever but never rewritten (a global) must not retain every EXEC."""
        lst = rd.setdefault(h, [])
        if len(lst) > 8:
            lst[:] = [f for f in lst if not f.done()]
        lst.append(fut)

    def _gate(self, device: int, item: _WorkItem,
              deps: Sequence["_cf.Future"]) -> None:
        """Hand the item to the worker once every dependency has settled.

        Settled means done — success *or* failure: dependencies order the
        stream, they do not gate on success (async failures surface at the
        next sync op, exactly as in the serial queue)."""
        if not deps:
            self._queues[device].put(item)
            return
        remaining = [len(deps)]
        lk = threading.Lock()

        def _one_done(_f: "_cf.Future") -> None:
            with lk:
                remaining[0] -= 1
                if remaining[0]:
                    return
            self._queues[device].put(item)

        for f in deps:
            f.add_done_callback(_one_done)

    def _submit(self, device: int, fn: Callable[[], Any], *,
                reads: Sequence[int] = (), writes: Sequence[int] = (),
                extra_deps: Sequence["_cf.Future"] = ()) -> "_cf.Future":
        # stopped-check and registration are atomic under the issue lock so
        # no item can land behind stop_all's close sentinel (a worker that
        # already exited would leave the submitter blocked forever)
        with self.locks[device]:
            if self._stopped[device]:
                raise DeviceStoppedError(f"device {device} is stopped")
            fut: "_cf.Future" = _cf.Future()
            deps = self._stream_deps(device, fut, reads, writes, extra_deps)
            out = self._outstanding[device]
            if len(out) > 64:                # prune settled commands in place
                out[:] = [f for f in out if not f.done()]
            out.append(fut)
        self._gate(device, _WorkItem(fn, fut), deps)
        return fut

    def _submit_async(self, device: int, fn: Callable[[], Any], *,
                      reads: Sequence[int] = (), writes: Sequence[int] = (),
                      extra_deps: Sequence["_cf.Future"] = ()) -> "_cf.Future":
        """Enqueue fire-and-forget; failures surface at the next sync op."""
        fut = self._submit(device, fn, reads=reads, writes=writes,
                           extra_deps=extra_deps)

        def _stash(f: "_cf.Future") -> None:
            err = f.exception()
            if err is not None and self._async_errors[device] is None:
                self._async_errors[device] = err

        fut.add_done_callback(_stash)
        return fut

    def _raise_async(self, device: int) -> None:
        err, self._async_errors[device] = self._async_errors[device], None
        if err is not None:
            raise err

    def _await_deadline(self, device: int, fut: "_cf.Future", cmd: Command):
        """Block on a value-producing command under the pool deadline.

        The deadline is end-to-end (queue wait + dependency gating +
        execution): a command starved behind a wedged producer is just as
        much a straggler as a slow one.  A blown deadline raises
        :class:`StragglerTimeout`; the command itself is NOT cancelled — it
        settles whenever the worker gets to it, and recovery routes around
        it in the meantime.
        """
        if self.deadline_s is None:
            return fut.result()
        try:
            return fut.result(timeout=self.deadline_s)
        except _cf.TimeoutError:
            with self._trace_lock:
                self.straggler_timeouts[cmd.op] = (
                    self.straggler_timeouts.get(cmd.op, 0) + 1)
            raise StragglerTimeout(
                f"{cmd.op} on device {device} exceeded the "
                f"{self.deadline_s}s command deadline",
                op=cmd.op, device=device,
                kernel_index=cmd.kernel_index) from None

    def absorb_failures(self) -> List[BaseException]:
        """Clear stashed *injected* async errors pool-wide; return them.

        Graph-level recovery handles :class:`DeviceFailure` itself (re-place,
        reroute, replay); leaving the stash armed would make an innocent
        region's next sync op steal the error.  Non-DeviceFailure errors are
        left in place — they surface as before.
        """
        absorbed: List[BaseException] = []
        for d in range(len(self.devices)):
            with self.locks[d]:
                err = self._async_errors[d]
                if isinstance(err, DeviceFailure):
                    self._async_errors[d] = None
                    absorbed.append(err)
        with self._trace_lock:
            self.failures_absorbed += len(absorbed)
        return absorbed

    def _traced(self, device: int, cmd: Command,
                fn: Callable[[], Any]) -> Callable[[], Any]:
        """Wrap ``fn`` to log the command in execution (not issue) order.

        No lock: only device ``d``'s single worker thread appends to
        ``stream_traces[d]`` (readers synchronize via :meth:`sync`)."""

        def run():
            self.stream_traces[device].append(cmd)
            return fn()

        return run

    def open_reader(self, device: int, handles: Sequence[int]) -> StreamTicket:
        """Register a reader of ``handles`` ahead of the EXEC that uses them.

        Returns a :class:`StreamTicket` whose ``deps`` are the handles' last
        writers at registration time (pass them to the EXEC via
        ``extra_deps``) and which, while open, blocks any later writer of
        the handles.  Call under the device's data-environment lock so no
        refresh can slip between a present-table match and the registration;
        close it (always — use try/finally) once the EXEC has consumed the
        content.
        """
        with self.locks[device]:
            lw, rd = self._last_write[device], self._readers[device]
            fut: "_cf.Future" = _cf.Future()
            deps: Dict[int, "_cf.Future"] = {}
            for h in handles:
                f = lw.get(h)
                if f is not None and not f.done():
                    deps[id(f)] = f
            for h in dict.fromkeys(handles):
                self._note_reader(rd, h, fut)
            return StreamTicket(list(deps.values()), fut)

    def sync(self, device: Optional[int] = None) -> None:
        """Barrier: wait until every command issued so far has settled."""
        devs = range(len(self.devices)) if device is None else [device]
        futs: List["_cf.Future"] = []
        for d in devs:
            with self.locks[d]:
                futs.extend(self._outstanding[d])
                self._outstanding[d][:] = [
                    f for f in self._outstanding[d] if not f.done()]
        if futs:
            _cf.wait(futs)
        for d in devs:
            self._raise_async(d)

    # -- construction --------------------------------------------------------
    @classmethod
    def from_config(cls, lines: Sequence[str], **kw) -> "DevicePool":
        devices: List[NodeDevice] = []
        local = jax.devices()[0]
        for line in lines:
            parts = line.split()
            if not parts or parts[0].startswith("#"):
                continue
            host = parts[0]
            mult = int(parts[1]) if len(parts) > 1 else 1
            for _ in range(mult):
                devices.append(NodeDevice(len(devices), jax_device=local, hostname=host))
        return cls(devices, **kw)

    @classmethod
    def virtual(cls, n: int, **kw) -> "DevicePool":
        """n virtual devices on the local chip (cluster simulation)."""
        return cls.from_config([f"vnode{i}" for i in range(n)], **kw)

    @classmethod
    def from_mesh_slices(cls, mesh: jax.sharding.Mesh, axis: str, **kw) -> "DevicePool":
        """One NodeDevice per index along ``axis`` of ``mesh`` (pod rows)."""
        import numpy as _np
        devs = _np.moveaxis(mesh.devices, mesh.axis_names.index(axis), 0)
        out = []
        for i in range(devs.shape[0]):
            sub = jax.sharding.Mesh(devs[i], tuple(a for a in mesh.axis_names if a != axis))
            sharding = jax.sharding.NamedSharding(sub, jax.sharding.PartitionSpec())
            out.append(NodeDevice(i, sharding=sharding, hostname=f"slice{i}"))
        return cls(out, **kw)

    def __len__(self) -> int:
        return len(self.devices)

    # -- command issue (host side) -------------------------------------------
    def _log(self, cmd: Command) -> None:
        with self._trace_lock:
            self.trace.append(cmd)

    def alloc(self, device: int, shape: Sequence[int], dtype: Any, tag: str = "") -> int:
        with self.locks[device]:
            handle = self.mirrors[device].reserve(shape, dtype)  # 0x999 mark
            cmd = Command("ALLOC", device, handle=handle,
                          nbytes=self.mirrors[device].nbytes(handle), tag=tag,
                          writes=(handle, SLOT_STREAM))
            self._log(cmd)
            payload = {"shape": tuple(shape), "dtype": dtype}
            self._submit_async(
                device,
                self._traced(device, cmd,
                             lambda: self.devices[device].execute(cmd, self.table, payload)),
                writes=cmd.writes)
            return handle

    def free(self, device: int, handle: int) -> None:
        with self.locks[device]:
            self.mirrors[device].free(handle)
            cmd = Command("FREE", device, handle=handle,
                          writes=(handle, SLOT_STREAM))
            self._log(cmd)
            self._submit_async(
                device,
                self._traced(device, cmd,
                             lambda: self.devices[device].execute(cmd, self.table)),
                writes=cmd.writes)

    def transfer_to(self, device: int, handle: int, value: Any,
                    section: Optional[slice] = None, tag: str = "") -> "_cf.Future":
        # copy at issue time (a later change to a host array must not reach
        # the device), straight onto the target device
        value = self.devices[device]._place(host_array(value))
        nbytes = value.size * value.dtype.itemsize
        with self.locks[device]:
            cmd = Command("XFER_TO", device, handle=handle, nbytes=nbytes,
                          tag=tag, writes=(handle,))
            self._log(cmd)
            self.cost.record_transfer("to", device, nbytes, tag=tag)
            payload = {"value": value, "section": section}
            return self._submit_async(
                device,
                self._traced(device, cmd,
                             lambda: self.devices[device].execute(cmd, self.table, payload)),
                writes=cmd.writes)

    def transfer_from(self, device: int, handle: int,
                      section: Optional[slice] = None, tag: str = "") -> jax.Array:
        with self.locks[device]:
            cmd = Command("XFER_FROM", device, handle=handle, tag=tag,
                          reads=(handle,))
            self._log(cmd)
            payload = {"section": section}
            fut = self._submit(
                device,
                self._traced(device, cmd,
                             lambda: jax.block_until_ready(
                                 self.devices[device].execute(cmd, self.table, payload))),
                reads=cmd.reads)
        out = self._await_deadline(device, fut, cmd)
        self._raise_async(device)
        nbytes = out.size * out.dtype.itemsize
        self.cost.record_transfer("from", device, nbytes, tag=tag)
        return out

    def transfer_to_writeback(self, device: int, handle: int, value: Any) -> "_cf.Future":
        """Device-local write-back of a kernel result (no host↔device traffic).

        A writer of ``handle`` in the device stream: it runs after the
        region's EXEC (a registered reader) and before any later consumer.
        """
        value = jnp.asarray(value)

        def wb():
            dev = self.devices[device]
            dev.store.free(handle)
            dev.store.install(handle, dev._place(value))

        return self._submit_async(device, wb, writes=(handle,))

    def peer_copy(self, src: int, src_handle: int, dst: int, dst_handle: int,
                  *, nbytes: Optional[int] = None, tag: str = "") -> "_cf.Future":
        """Device→device copy: a SEND on ``src``'s stream rendezvousing with
        a RECV on ``dst``'s stream — the transfer never touches the host
        funnel (accounted as peer-link traffic instead).

        Ordering composes with ``nowait`` and resident buffers exactly like
        XFER/EXEC: SEND *reads* ``src_handle`` (runs after its last producer,
        holds back its next writer) and RECV *writes* ``dst_handle``.  The
        rendezvous itself is a cross-stream dependency edge — RECV is gated
        on the SEND future, so the destination worker is handed the command
        only once the payload exists.  Because that edge always points from
        an earlier-issued command to a later-issued one, no cycle can form:
        any interleaving of peer copies (including full rings) is
        deadlock-free by construction.

        ``nbytes`` overrides the accounted message size (modeled wire
        compression); the payload itself always moves intact.  Returns the
        RECV future (a registered writer of ``dst_handle``); SEND failures
        propagate through it.
        """
        if src == dst:
            raise ValueError(f"peer_copy: src and dst are both device {src}")
        wire = self.mirrors[src].nbytes(src_handle) if nbytes is None else int(nbytes)
        with self.locks[src]:
            scmd = Command("SEND", src, handle=src_handle, nbytes=wire,
                           tag=tag, peer=dst, reads=(src_handle,))
            self._log(scmd)
            send_fut = self._submit_async(
                src,
                self._traced(src, scmd,
                             lambda: self.devices[src].execute(scmd, self.table)),
                reads=scmd.reads)
        with self.locks[dst]:
            rcmd = Command("RECV", dst, handle=dst_handle, nbytes=wire,
                           tag=tag, peer=src, writes=(dst_handle,))
            self._log(rcmd)
            payload = {"source": send_fut}
            recv_fut = self._submit_async(
                dst,
                self._traced(dst, rcmd,
                             lambda: self.devices[dst].execute(rcmd, self.table,
                                                               payload)),
                writes=rcmd.writes, extra_deps=(send_fut,))
        self.cost.record_peer(src, dst, wire, tag=tag)
        return recv_fut

    def exec_kernel(self, device: int, kernel_name: str,
                    buffers: Dict[str, Any],
                    firstprivate: Optional[Dict[str, Any]] = None,
                    trees: Optional[Dict[str, Any]] = None,
                    static_argnames: Sequence[str] = (), tag: str = "",
                    skip_reads: Sequence[int] = (),
                    extra_deps: Sequence["_cf.Future"] = ()) -> Any:
        """Run a kernel; reads are derived from the mapped buffer handles.

        ``skip_reads`` names handles an open :class:`StreamTicket` already
        covers — registering them again would deadlock on a writer that is
        itself waiting on the ticket; their ordering arrives via
        ``extra_deps`` (the ticket's captured last-writer futures) instead.
        """
        index = self.table.index_of(kernel_name)   # name → wire integer
        all_handles: List[int] = []
        for h in buffers.values():
            all_handles.extend(h if isinstance(h, (list, tuple)) else [h])
        skip = set(skip_reads)
        reads = tuple(h for h in all_handles if h not in skip)
        with self.locks[device]:
            cmd = Command("EXEC", device, kernel_index=index,
                          tag=tag or kernel_name, reads=tuple(all_handles))
            self._log(cmd)
            payload = {"buffers": buffers, "firstprivate": firstprivate or {},
                       "trees": trees or {},
                       "static_argnames": tuple(static_argnames)}

            def run_exec():
                t0 = time.perf_counter()
                out = self.devices[device].execute(cmd, self.table, payload)
                out = jax.block_until_ready(out)
                return out, time.perf_counter() - t0

            fut = self._submit(device, self._traced(device, cmd, run_exec),
                               reads=reads, extra_deps=extra_deps)
        out, seconds = self._await_deadline(device, fut, cmd)
        self._raise_async(device)
        self.cost.record_compute(device, seconds, tag=tag or kernel_name,
                                 kernel=kernel_name)
        return out

    def _stop_device(self, i: int) -> Optional["_cf.Future"]:
        """Close device ``i``'s stream: gate a STOP on everything in flight,
        mark the queue refused, and schedule the worker-exit sentinel."""
        with self.locks[i]:                  # atomic with any in-flight issue
            if self._stopped[i]:
                return None
            cmd = Command("STOP", i)
            self._log(cmd)
            # STOP runs after every outstanding command has settled;
            # _submit would refuse once the stopped flag is up, so gate
            # it by hand on a snapshot of the in-flight futures.
            deps = [f for f in self._outstanding[i] if not f.done()]
            fut: "_cf.Future" = _cf.Future()
            self._outstanding[i].append(fut)
            self._stopped[i] = True
        self._gate(i, _WorkItem(
            self._traced(i, cmd,
                         lambda i=i, cmd=cmd: self.devices[i].execute(cmd, self.table)),
            fut), deps)
        # worker exits once STOP has executed; nothing can trail it
        # (every earlier command is a dependency of STOP, and the
        # stopped flag refuses new submissions)
        fut.add_done_callback(lambda _f, i=i: self._queues[i].put(None))
        return fut

    def stop_all(self) -> None:
        futs = [self._stop_device(d.index) for d in self.devices]
        for f in futs:
            if f is not None:
                f.result()

    # -- elastic pool membership (beyond-paper: nodes join/leave mid-job) -----
    def add_device(self, hostname: Optional[str] = None,
                   capacity_bytes: Optional[int] = None) -> int:
        """Grow the pool by one device, placeable immediately.

        Appends every piece of per-device parallel state, starts the worker
        thread, and replays ``install_global`` history onto the newcomer so
        declare-target globals resolve there too.  Returns the new index.

        The newcomer is one more share of the placement every device of the
        pool already shares (virtual shares of one chip).  A pool spread
        over several chips raises: no chip is the obvious home for it.
        """
        i = len(self.devices)
        homes = {(d.jax_device, d.sharding) for d in self.devices}
        if len(homes) > 1:
            raise ValueError(f"cannot grow a pool spread over {len(homes)} "
                             f"placements")
        jax_device, sharding = next(iter(homes), (jax.devices()[0], None))
        dev = NodeDevice(i, jax_device=jax_device, sharding=sharding,
                         hostname=hostname or f"vnode{i}",
                         capacity_bytes=capacity_bytes)
        self.devices.append(dev)
        self.mirrors.append(HostMirror())
        self.locks.append(threading.RLock())
        self.present.append(PresentTable(capacity_bytes=(
            capacity_bytes if capacity_bytes is not None
            else self._default_capacity)))
        self.env_locks.append(threading.RLock())
        self._queues.append(queue.SimpleQueue())
        self._stopped.append(False)
        self._async_errors.append(None)
        self._last_write.append({})
        self._readers.append({})
        self._outstanding.append([])
        self.stream_traces.append(collections.deque(maxlen=4096))
        t = threading.Thread(target=self._worker, args=(i,),
                             name=f"omp-dev{i}", daemon=True)
        t.start()
        self._workers.append(t)
        self.health.mark_healthy(i)          # fresh device, clean slate
        # declare-target globals must exist on every device (paper §4.2)
        for name, value in self._global_values.items():
            h = self.alloc(i, value.shape, value.dtype, tag=f"global:{name}")
            self.transfer_to(i, h, value, tag=f"global:{name}")
            self.globals[name][i] = h
        return i

    def remove_tail(self, count: int) -> None:
        """Shrink the pool by its last ``count`` devices.

        Callers must have drained the departing devices' present tables
        first (see ``ft.elastic.rescale_pool``); this only closes streams
        and truncates the parallel state lists.
        """
        if count <= 0:
            return
        n = len(self.devices)
        if count >= n:
            raise ValueError("cannot remove every device from the pool")
        departing = list(range(n - count, n))
        futs = [self._stop_device(i) for i in departing]
        for f in futs:
            if f is not None:
                f.result()
        for i in departing:
            self._raise_async(i)             # surface anything left stashed
            for handles in self.globals.values():
                handles.pop(i, None)
            self.health.mark_healthy(i)      # stale marks must not outlive it
        keep = n - count
        del self.devices[keep:]
        del self.mirrors[keep:]
        del self.locks[keep:]
        del self.present[keep:]
        del self.env_locks[keep:]
        del self._queues[keep:]
        del self._stopped[keep:]
        del self._async_errors[keep:]
        del self._last_write[keep:]
        del self._readers[keep:]
        del self._outstanding[keep:]
        del self.stream_traces[keep:]
        del self._workers[keep:]

    # -- declare-target globals (paper §4.2 last ¶) ---------------------------
    def install_global(self, name: str, value: Any, tag: str = "") -> int:
        """Install a global on EVERY device, pre-user-code.

        Paper: "All nodes place the addresses of global variables in their
        arrays at the beginning of the execution and in the same order."
        When installation really does precede all user allocations the
        first-fit handles agree across devices; a buffer already pinned on
        one device (``ensure_resident``) shifts that device's slot, so the
        handle is tracked per device.  Returns device 0's handle.  The
        one-shot broadcast cost is recorded (it is what makes the alignment
        workload scale: invariant data moves once).
        """
        value = host_array(value)
        if name in self.globals:            # idempotent re-install (re-runs)
            old = self.globals.pop(name)
            for i, h in old.items():
                self.free(i, h)
        handles: Dict[int, int] = {}
        for i in range(len(self.devices)):
            h = self.alloc(i, value.shape, value.dtype, tag=f"global:{name}")
            self.transfer_to(i, h, value, tag=tag or f"global:{name}")
            handles[i] = h
        self.globals[name] = handles
        self._global_values[name] = value
        return handles[0]
