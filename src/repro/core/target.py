"""``target()`` offload regions with OpenMP ``map`` semantics (paper §3).

An OpenMP target region names a kernel, a device, and a set of ``map``
clauses.  We mirror that exactly:

* ``map(to=...)``      — value copied host → device before execution,
* ``map(from_=...)``   — value copied device → host after execution,
* ``map(tofrom=...)``  — both,
* ``map(alloc=...)``   — device allocation, no transfer either way,
* ``firstprivate``     — small scalars passed by value in the EXEC message,
* array *sections* — ``sec(array, start, length)`` moves only a sub-array
  (paper Listing 2: "only the required 128 elements of each array are copied
  per device, using appropriate array sections").

JAX is functional, so instead of mutating mapped buffers the kernel returns a
dict ``{name: new_value}`` for every ``from_``/``tofrom`` name; the runtime
writes results back into the mediary store and transfers them to the host.

``nowait=True`` returns a :class:`TargetFuture`; the host thread continues and
may offload concurrently — to other devices, or to the *same* device: the
pool's dependency-aware stream orders commands per buffer handle, so two
regions sharing a resident name serialize exactly where their data
dependencies demand and nowhere else.  ``taskwait()`` joins everything;
``drain(futs)`` joins exactly the given futures (scoped — concurrent callers'
in-flight regions are untouched) and always waits for all of them to settle
before retiring them.

Beyond the four OpenMP map types, ``MapSpec.present`` names buffers that
MUST already be resident (OpenMP's ``present`` modifier: the handles bind
directly, no host value travels) and ``MapSpec.device_out`` names outputs
written back into a present entry **on the device** and not fetched — the
entry is marked *device-ahead* until :meth:`TargetExecutor.fetch_resident`
reconciles it.  Together they let a kernel chain state fully on-device
(``ClusterRuntime.data_parallel_step``'s fused grad+AdamW update).

Device data environments (OpenMP ``target data`` / ``target enter data``):
:meth:`TargetExecutor.enter_data` pins named buffers on a device in the
pool's reference-counted *present table*.  A later region whose map clause
names a present buffer with the **same host value** skips ALLOC and XFER
entirely — transfer elision.  When the host value changed (a new array
object: JAX arrays are immutable), only the changed leaves are re-sent and
the entry's content version bumps.  :meth:`target_data` is the scoped
context-manager form; nesting increments the refcount, and the buffer is
freed when the count drops to zero.
"""
from __future__ import annotations

import concurrent.futures as _cf
import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .device import (DeviceFailure, DevicePool, DeviceStoppedError,
                     StreamTicket, host_array)
from .mediary import PresentEntry, same_treedef
from .spans import REGION, span


@dataclass(frozen=True)
class Section:
    """An OpenMP array section ``a[start:start+length]`` along axis 0."""

    array: Any
    start: int
    length: int

    @property
    def value(self):
        return host_array(self.array)[self.start:self.start + self.length]

    @property
    def slice(self) -> slice:
        return slice(self.start, self.start + self.length)


def sec(array: Any, start: int, length: int) -> Section:
    return Section(array, start, length)


@dataclass
class MapSpec:
    """The map clauses of one target region."""

    to: Dict[str, Any] = field(default_factory=dict)
    from_: Dict[str, Any] = field(default_factory=dict)     # name -> ShapeDtypeStruct | array template
    tofrom: Dict[str, Any] = field(default_factory=dict)
    alloc: Dict[str, jax.ShapeDtypeStruct] = field(default_factory=dict)
    firstprivate: Dict[str, Any] = field(default_factory=dict)
    use_globals: Tuple[str, ...] = ()                       # declare-target vars, no transfer
    # OpenMP's ``present`` map-type modifier: the name MUST already be
    # resident on the device; its handles bind directly (no host value
    # travels, so it works even when the device copy is ahead of the host).
    # Either a tuple of names, or a dict aliasing the kernel's parameter
    # name to a (possibly namespaced) present-table entry name — so a
    # runtime can pin e.g. "__dps_params" without colliding with a user's
    # own "params" data environment.
    present: Any = ()                  # Tuple[str, ...] | Dict[str, str]
    # device-resident outputs: the kernel must return these names, the
    # result is written back into the (required-present) entry on-device
    # and NOT fetched — the entry is marked device-ahead instead.  Same
    # alias forms as ``present``.
    device_out: Any = ()               # Tuple[str, ...] | Dict[str, str]

    def all_names(self) -> List[str]:
        return (list(self.to) + list(self.from_) + list(self.tofrom)
                + list(self.alloc) + list(self.use_globals)
                + list(_alias_map(self.present)) + list(_alias_map(self.device_out)))


class TargetFuture:
    """Handle to an in-flight ``nowait`` region."""

    def __init__(self, fut: _cf.Future) -> None:
        self._fut = fut

    def result(self) -> Dict[str, jax.Array]:
        return self._fut.result()

    def done(self) -> bool:
        return self._fut.done()


def _as_spec(x: Any) -> jax.ShapeDtypeStruct:
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    a = host_array(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _alias_map(x: Any) -> Dict[str, str]:
    """Normalize a present/device_out clause: kernel kwarg -> entry name."""
    if isinstance(x, Mapping):
        return dict(x)
    return {n: n for n in x}


def _flatten_map_value(val: Any) -> Tuple[List[Any], Any]:
    """(leaves, treedef|None): None treedef = plain single array."""
    if isinstance(val, (Section, jax.ShapeDtypeStruct)) or hasattr(val, "shape"):
        return [val], None
    leaves, treedef = jax.tree.flatten(
        val, is_leaf=lambda x: isinstance(x, (Section, jax.ShapeDtypeStruct)))
    if treedef.num_leaves == 1 and jax.tree.structure(0) == treedef:
        return leaves, None
    return leaves, treedef


class TargetExecutor:
    """Executes target regions against a :class:`DevicePool`."""

    def __init__(self, pool: DevicePool, max_host_threads: int = 16) -> None:
        self.pool = pool
        self._tp = _cf.ThreadPoolExecutor(max_workers=max_host_threads,
                                          thread_name_prefix="omp-host")
        self._inflight: List[TargetFuture] = []
        self._inflight_lock = threading.Lock()

    # -- the target construct -------------------------------------------------
    def target(self, kernel: str, device: int, maps: MapSpec, *,
               nowait: bool = False, tag: str = "",
               work: Optional[Dict[str, float]] = None
               ) -> Union[Dict[str, jax.Array], TargetFuture]:
        """Run one region.  ``work`` is what the caller declares its kernel
        computes (``TaskNode.work``); it goes onto the EXEC's compute
        record."""
        if nowait:
            fut = TargetFuture(self._tp.submit(self._run, kernel, device,
                                               maps, tag, work))
            with self._inflight_lock:
                self._inflight.append(fut)
            return fut
        return self._run(kernel, device, maps, tag, work)

    def taskwait(self) -> List[Dict[str, jax.Array]]:
        with self._inflight_lock:
            futs = list(self._inflight)
        return self.drain(futs)

    def drain(self, futs: Iterable[TargetFuture]) -> List[Dict[str, jax.Array]]:
        """Join exactly ``futs`` and retire them from the in-flight list.

        Scoped replacement for clearing the whole in-flight list: concurrent
        callers' regions keep their registration, so a later ``taskwait``
        still joins them.
        """
        futs = list(futs)
        try:
            return [f.result() for f in futs]
        finally:
            # an early failure must not retire still-running regions: they
            # would keep executing unjoined against state the caller may
            # tear down — wait for every future to settle first.  Retire
            # even the failed ones: a settled-but-failed future left
            # registered would re-raise at an unrelated later taskwait.
            if futs:
                _cf.wait([f._fut for f in futs])
            self.retire(futs)

    def retire(self, futs: Iterable[TargetFuture]) -> None:
        """Remove already-settled futures from the in-flight list."""
        with self._inflight_lock:
            ids = {id(f) for f in futs}
            self._inflight = [f for f in self._inflight if id(f) not in ids]

    # -- device data environments (OpenMP target data, paper §3) --------------
    def enter_data(self, device: int, _tag: str = "enter_data", /,
                   **values: Any) -> None:
        """``target enter data``: make named buffers resident on ``device``.

        ``device`` and the tag are positional-only so buffer names can never
        collide with them.  Already-present names gain a reference; their
        device copy is refreshed (changed leaves only) if the host value is
        a different object.  Pair every ``enter_data`` with an
        :meth:`exit_data`.  All-or-nothing: if a later name fails (shape
        mismatch), references already taken by this call are unwound.
        """
        entered: List[str] = []
        try:
            for name, val in values.items():
                self._enter_one(device, name, val, retain=True, tag=_tag)
                entered.append(name)
        except BaseException:
            if entered:
                self.exit_data(device, *entered)
            raise

    def ensure_resident(self, device: int, _tag: str = "resident", /,
                        **values: Any) -> None:
        """Idempotent residency: enter once, afterwards only refresh.

        Unlike :meth:`enter_data`, repeated calls do not accumulate
        references — the buffer stays pinned with refcount 1 until an
        explicit :meth:`exit_data`.  This is the steady-state API for
        invariant data used every iteration (e.g. model parameters).
        """
        for name, val in values.items():
            self._enter_one(device, name, val, retain=False, tag=_tag)

    def _enter_one(self, device: int, name: str, val: Any, *,
                   retain: bool, tag: str) -> None:
        pool = self.pool
        leaves, treedef = _flatten_map_value(val)
        if any(isinstance(l, Section) for l in leaves):
            raise TypeError(f"array section {name!r} cannot be made resident")
        with pool.env_locks[device]:
            ent = pool.present[device].get(name)
            if ent is None:
                # convert before allocating: a bad leaf must fail with zero
                # device state, and the capacity reservation needs the size
                vals = [host_array(leaf) for leaf in leaves]
                self._reserve_capacity(
                    device, sum(v.size * v.dtype.itemsize for v in vals),
                    tag=tag)
                hs, specs, hosts, wfuts = [], [], [], []
                try:
                    for leaf, v in zip(leaves, vals):
                        h = pool.alloc(device, v.shape, v.dtype, tag=f"{tag}:{name}")
                        hs.append(h)
                        wfuts.append(pool.transfer_to(device, h, v,
                                                      tag=f"{tag}:{name}"))
                        specs.append(jax.ShapeDtypeStruct(v.shape, v.dtype))
                        hosts.append(leaf)
                except BaseException:
                    # a later leaf failed (stopped device): free the
                    # allocations already made so nothing leaks on the
                    # device or its mirror
                    with contextlib.suppress(DeviceStoppedError):
                        for h in hs:
                            pool.free(device, h)
                    raise
                entry = PresentEntry(
                    name=name, handles=hs, treedef=treedef,
                    host_leaves=hosts, specs=specs, write_futs=wfuts)
                entry.debit = entry.nbytes()
                pool.present[device].add(entry)
            else:
                # refresh (or revive a spilled entry) first: a structure-
                # mismatch error must not leak a reference (the caller never
                # sees the entry as entered)
                if ent.spilled:
                    self._revive(device, ent, leaves, treedef, tag)
                else:
                    self._refresh(device, ent, leaves, treedef, tag)
                pool.present[device].touch(ent)
                if retain:
                    ent.refcount += 1

    def _refresh(self, device: int, ent: PresentEntry, leaves: List[Any],
                 treedef: Any, tag: str) -> None:
        """Re-send only the leaves whose host value changed (version bump).

        Validates every leaf before moving any bytes, so a mismatch raises
        with the entry untouched.  Elision stats are counted at map-match
        time only (``PresentTable.match_value``), not here — an unchanged
        leaf in a refresh is not a transfer the seed would have made.
        """
        pool = self.pool
        if not same_treedef(ent.treedef, treedef) or len(ent.host_leaves) != len(leaves):
            raise ValueError(
                f"resident buffer {ent.name!r} structure changed; "
                f"exit_data it first")
        stale = []
        for i, leaf in enumerate(leaves):
            # mutable host arrays (numpy) can change under the same identity,
            # so only immutable jax.Array leaves count as unchanged; and a
            # refresh of a device-ahead entry re-sends EVERY leaf (host-
            # authoritative overwrite) — a partial push would leave the
            # device a mix of host and device-advanced content
            if (not ent.device_ahead and leaf is ent.host_leaves[i]
                    and isinstance(leaf, jax.Array)):
                continue
            v = host_array(leaf)
            if v.shape != ent.specs[i].shape or v.dtype != jnp.dtype(ent.specs[i].dtype):
                raise ValueError(
                    f"resident buffer {ent.name!r} leaf {i} changed "
                    f"shape/dtype {ent.specs[i]} -> {v.shape}/{v.dtype}; "
                    f"exit_data it first")
            stale.append((i, leaf, v))
        for i, leaf, v in stale:
            fut = pool.transfer_to(device, ent.handles[i], v,
                                   tag=f"{tag}:{ent.name}")
            if i < len(ent.write_futs):
                ent.write_futs[i] = fut
            ent.host_leaves[i] = leaf
            ent.debit += int(np.prod(ent.specs[i].shape, dtype=np.int64)
                             * jnp.dtype(ent.specs[i].dtype).itemsize)
        if stale:
            ent.version += 1
            ent.device_ahead = False       # the host push wins from here on

    def _alloc_specs(self, device: int, specs: Sequence[jax.ShapeDtypeStruct],
                     tag: str) -> List[int]:
        """ALLOC one handle per spec; on failure free the ones already made."""
        pool = self.pool
        hs: List[int] = []
        try:
            for s in specs:
                hs.append(pool.alloc(device, s.shape, s.dtype, tag=tag))
        except BaseException:
            with contextlib.suppress(DeviceStoppedError):
                for h in hs:
                    pool.free(device, h)
            raise
        return hs

    # -- capacity-bounded residency: LRU spill + transparent refetch ----------
    def _spill_locked(self, device: int, ent: PresentEntry, tag: str) -> None:
        """Free ``ent``'s device buffers but keep the logical entry (spill).

        Caller holds ``env_locks[device]``.  Device-ahead content — and
        ``alloc_resident`` buffers whose host view is still a placeholder —
        is reconciled to the host *before* the buffers are freed, so a spill
        can never lose a value: the failure-free path the capacity bound
        rides on.  The reconcile fetch and the eventual refetch are ordinary
        stream commands, ordered after the entry's in-flight writers.
        """
        pool = self.pool
        table = pool.present[device]
        if ent.device_ahead or any(l is None for l in ent.host_leaves):
            fetched = [pool.transfer_from(device, h,
                                          tag=f"{tag}:reconcile:{ent.name}")
                       for h in ent.handles]
            ent.host_leaves = list(fetched)
            ent.device_ahead = False
            table.bytes_reconciled += ent.nbytes()
        for h in ent.handles:
            pool.free(device, h)
        ent.handles = []
        ent.write_futs = []
        ent.debit = 0
        ent.spilled = True
        table.evictions += 1

    def _reserve_capacity(self, device: int, nbytes: int, *,
                          tag: str = "capacity",
                          protect: Sequence[str] = ()) -> None:
        """Make room for ``nbytes`` more resident bytes; caller holds env lock.

        Evicts least-recently-used entries (skipping pinned entries,
        ``protect`` names, and anything an in-flight region retains) until
        the budget fits.  Soft cap: when nothing is evictable the residency
        proceeds over budget rather than failing — capacity pressure must
        never change a program's result, only its traffic.
        """
        table = self.pool.present[device]
        if table.capacity_bytes is None:
            return
        while table.used_bytes() + nbytes > table.capacity_bytes:
            victim = table.lru_victim(protect)
            if victim is None:
                break
            self._spill_locked(device, victim, tag)

    def _refetch_locked(self, device: int, ent: PresentEntry, tag: str) -> None:
        """Re-materialize a spilled entry from its host view.

        Caller holds ``env_locks[device]``.  The transparent half of the
        spill path: a binding that *requires* residency (``present`` /
        ``device_out`` maps, a peer propagation source) finds the entry
        spilled, and this re-allocates and re-sends it — possibly evicting
        someone else to make room.
        """
        pool = self.pool
        table = pool.present[device]
        self._reserve_capacity(device, ent.nbytes(), tag=tag,
                               protect=(ent.name,))
        hs = self._alloc_specs(device, ent.specs, f"{tag}:refetch:{ent.name}")
        ent.handles = hs
        ent.write_futs = [pool.transfer_to(device, h, leaf,
                                           tag=f"{tag}:refetch:{ent.name}")
                          for h, leaf in zip(hs, ent.host_leaves)]
        ent.spilled = False
        ent.version += 1
        ent.debit = ent.nbytes()   # the refetch re-paid the entry's transfer
        table.refetches += 1
        table.bytes_refetched += ent.nbytes()
        table.touch(ent)

    def _heal_locked(self, device: int, ent: PresentEntry, tag: str) -> None:
        """Repair a resident entry whose last writer failed (injected fault).

        Caller holds ``env_locks[device]``.  A failed XFER_TO/RECV leaves the
        device buffer unwritten while the entry still *looks* bound; a region
        that matched it would compute on garbage.  When the entry has an
        authoritative host view, re-send it (self-healing pin); when it does
        not (device-ahead, or an ``alloc_resident`` placeholder), raise the
        stored :class:`DeviceFailure` so graph-level recovery re-propagates
        or replays the producer.  Non-injected errors always re-raise.
        """
        pool = self.pool
        for i, f in enumerate(ent.write_futs):
            if f is None or not f.done():
                continue
            err = f.exception()
            if err is None:
                continue
            if not isinstance(err, DeviceFailure):
                raise err
            leaf = (ent.host_leaves[i]
                    if i < len(ent.host_leaves) else None)
            if ent.device_ahead or leaf is None:
                # the write never landed and the host holds no copy: the
                # entry is unrecoverable on this device.  Drop it (free the
                # buffers, strike the name) so graph-level recovery replays
                # the producer / re-propagates the edge instead of
                # re-binding the same corpse on every retry.
                for h in ent.handles:
                    pool.free(device, h)
                ent.handles = []
                ent.write_futs = []
                pool.present[device].pop_entry(ent.name)
                with pool.locks[device]:
                    if pool._async_errors[device] is err:
                        pool._async_errors[device] = None
                raise err
            ent.write_futs[i] = pool.transfer_to(
                device, ent.handles[i], leaf,
                tag=f"{tag}:heal:{ent.name}")
            ent.version += 1
            # the failure is handled; don't let an innocent sync op trip it
            with pool.locks[device]:
                if pool._async_errors[device] is err:
                    pool._async_errors[device] = None

    def _revive(self, device: int, ent: PresentEntry, leaves: List[Any],
                treedef: Any, tag: str) -> None:
        """Refresh a *spilled* entry with a (possibly new) host value."""
        if not same_treedef(ent.treedef, treedef) or len(ent.host_leaves) != len(leaves):
            raise ValueError(
                f"resident buffer {ent.name!r} structure changed; "
                f"exit_data it first")
        for i, leaf in enumerate(leaves):
            v = host_array(leaf)
            if v.shape != ent.specs[i].shape or v.dtype != jnp.dtype(ent.specs[i].dtype):
                raise ValueError(
                    f"resident buffer {ent.name!r} leaf {i} changed "
                    f"shape/dtype {ent.specs[i]} -> {v.shape}/{v.dtype}; "
                    f"exit_data it first")
        ent.host_leaves = list(leaves)
        self._refetch_locked(device, ent, tag)

    def _maybe_revive_value(self, device: int, name: str, leaves: List[Any],
                            treedef: Any, tag: str) -> None:
        """Refetch a spilled entry that would value-match ``leaves``.

        Caller holds ``env_locks[device]``.  Without this, a spilled entry
        would miss the match and go stale relative to the uncapped run
        (whose hit keeps the entry live through the region's write-back) —
        the cap must change traffic, never any later ``fetch_resident``.
        """
        ent = self.pool.present[device].get(name)
        if ent is None or not ent.spilled or ent.device_ahead:
            return
        if (same_treedef(ent.treedef, treedef)
                and len(ent.host_leaves) == len(leaves)
                and all(a is b and isinstance(b, jax.Array)
                        for a, b in zip(ent.host_leaves, leaves))):
            self._refetch_locked(device, ent, tag)

    def _maybe_revive_specs(self, device: int, name: str,
                            specs: Sequence[jax.ShapeDtypeStruct],
                            treedef: Any, tag: str) -> None:
        """Refetch a spilled entry that would spec-match (output reuse).

        Caller holds ``env_locks[device]``.  The content comes back too, not
        just fresh handles: a kernel that declares its output name as a
        parameter reads the buffer's prior value, exactly as it would have
        without the cap.
        """
        ent = self.pool.present[device].get(name)
        if ent is None or not ent.spilled:
            return
        if (same_treedef(ent.treedef, treedef)
                and len(ent.specs) == len(specs)
                and all(a.shape == b.shape
                        and jnp.dtype(a.dtype) == jnp.dtype(b.dtype)
                        for a, b in zip(ent.specs, specs))):
            self._refetch_locked(device, ent, tag)

    def pin_resident(self, device: int, *names: str, pinned: bool = True) -> None:
        """Exempt resident entries from capacity eviction (or re-admit them)."""
        with self.pool.env_locks[device]:
            for name in names:
                ent = self.pool.present[device].get(name)
                if ent is None:
                    raise KeyError(f"{name!r} is not resident on device {device}")
                ent.pinned = pinned

    def alloc_resident(self, device: int, name: str, template: Any, *,
                       tag: str = "alloc_resident") -> None:
        """Pin an *uninitialized* buffer: ALLOC only, zero host transfer.

        The device-side output half of a data environment: the entry starts
        *device-ahead* (the host has no value for it — ``host_leaves`` are
        None placeholders, so value matches miss until a fetch reconciles),
        a kernel's ``device_out`` map writes it, a peer collective reduces
        it, and :meth:`fetch_resident` reads it back.  ``template`` is a
        value, ``ShapeDtypeStruct``, or pytree of either.
        """
        pool = self.pool
        leaves, treedef = _flatten_map_value(template)
        if any(isinstance(l, Section) for l in leaves):
            raise TypeError(f"array section {name!r} cannot be made resident")
        specs = [_as_spec(l) for l in leaves]
        with pool.env_locks[device]:
            if pool.present[device].get(name) is not None:
                raise KeyError(f"{name!r} is already resident on device {device}")
            self._reserve_capacity(
                device,
                sum(int(np.prod(s.shape, dtype=np.int64)) * jnp.dtype(s.dtype).itemsize
                    for s in specs), tag=tag)
            hs = self._alloc_specs(device, specs, f"{tag}:{name}")
            pool.present[device].add(PresentEntry(
                name=name, handles=hs, treedef=treedef,
                host_leaves=[None] * len(hs), specs=specs,
                write_futs=[None] * len(hs), device_ahead=True))

    def propagate_resident(self, src: int, dst: int, name: str, *,
                           transport: Any = None, tag: str = "peer",
                           compress_wire: bool = False) -> None:
        """Fulfill a present entry device→device: ``dst`` gains (or refreshes)
        entry ``name`` from ``src``'s device copy, without host reconciliation.

        This is the peer-path analogue of ``enter_data``: a *device-ahead*
        entry (a ``device_out`` result nothing has fetched) propagates to the
        peer still device-ahead — the host never sees the bytes.  If ``dst``
        already holds ``name`` (with matching structure), its handles are
        overwritten in place; otherwise fresh handles are allocated (ALLOC
        only) and the entry installed with one reference, owned by the
        caller.  ``transport`` defaults to a :class:`~repro.core.transport.
        PeerTransport`; pass a ``HostFunnelTransport`` to route the same
        fulfillment through the host NIC (the paper-faithful wire).

        ``compress_wire=True`` accounts each leaf's message at its
        block-int8 wire size (the transport topology's block, 256 without
        one) instead of the raw bytes — *modeled* wire compression: the
        payload itself still moves intact (``peer_copy``'s ``nbytes``
        override), so the destination's value is bit-identical either way.
        The graph runner sets this when the placement policy routed the
        edge ``"peer+int8"``.
        """
        if src == dst:
            return
        pool = self.pool
        if transport is None:
            from .transport import PeerTransport
            transport = PeerTransport()
        with pool.env_locks[src]:
            sent = pool.present[src].get(name)
            if sent is None:
                raise KeyError(f"{name!r} is not resident on device {src}")
            # a damaged source (failed refetch/refresh) must not propagate
            # garbage: heal from the host view or surface the stored failure
            self._heal_locked(src, sent, tag)
            sent.refcount += 1         # hold: a concurrent exit_data must not
                                       # free the source handles mid-copy
            # a spilled source holds no device bytes; its reconciled host
            # view is authoritative and fulfills dst straight from the host
            # (one funnel send) instead of refetching src only to re-send
            src_spilled = sent.spilled
            # snapshot under the src lock: `snap` is an immutable-by-
            # convention copy whose fields stay coherent after release
            src_handles = list(sent.handles)
            snap = sent.peer_clone(src_handles, [])
            specs, treedef = list(snap.specs), snap.treedef
        try:
            with pool.env_locks[dst]:
                dent = pool.present[dst].get(name)
                if dent is not None:
                    if (not same_treedef(dent.treedef, treedef)
                            or len(dent.specs) != len(specs)
                            or any(a.shape != b.shape
                                   or jnp.dtype(a.dtype) != jnp.dtype(b.dtype)
                                   for a, b in zip(dent.specs, specs))):
                        raise ValueError(
                            f"resident buffer {name!r} structure differs "
                            f"between devices {src} and {dst}; exit_data the "
                            f"stale one first")
                    if dent.spilled:
                        # about to be overwritten whole: fresh buffers, no
                        # stale-content refetch
                        self._reserve_capacity(dst, snap.nbytes(), tag=tag,
                                               protect=(name,))
                        dent.handles = self._alloc_specs(dst, specs,
                                                         f"{tag}:{name}")
                        dent.spilled = False
                    dst_handles = list(dent.handles)
                else:
                    self._reserve_capacity(dst, snap.nbytes(), tag=tag,
                                           protect=(name,))
                    dst_handles = self._alloc_specs(dst, specs, f"{tag}:{name}")
                if src_spilled:
                    futs = [pool.transfer_to(dst, dh, leaf,
                                             tag=f"{tag}:{name}")
                            for dh, leaf in zip(dst_handles, snap.host_leaves)]
                else:
                    wires: List[Optional[int]] = [None] * len(specs)
                    if compress_wire:
                        from . import compression as _comp
                        block = getattr(getattr(transport, "topology", None),
                                        "block", 256)
                        wires = [_comp.compressed_nbytes(jax.eval_shape(
                            lambda x: _comp.compress(x, block), s))
                            for s in specs]
                    futs = [transport.sendrecv(pool, src, sh, dst, dh,
                                               nbytes=w, tag=f"{tag}:{name}")
                            for (sh, dh), w in zip(zip(src_handles,
                                                       dst_handles), wires)]
                if dent is None:
                    pool.present[dst].add(snap.peer_clone(dst_handles, futs))
                else:
                    # refresh in place: the peer write is the new producer
                    dent.host_leaves = list(snap.host_leaves)
                    dent.device_ahead = snap.device_ahead
                    dent.write_futs = futs
                    dent.version += 1
                    pool.present[dst].touch(dent)
        finally:
            self.exit_data(src, name)  # release the hold taken above

    def exit_data(self, device: int, *names: str) -> None:
        """``target exit data``: drop one reference; free at zero."""
        pool = self.pool
        dead: List[PresentEntry] = []
        with pool.env_locks[device]:
            for name in names:
                e = pool.present[device].release(name)
                if e is not None:
                    dead.append(e)
        for e in dead:
            for h in e.handles:
                pool.free(device, h)

    @contextlib.contextmanager
    def target_data(self, device: int, /, **values: Any):
        """Scoped data environment (OpenMP ``target data`` region).

        Regions executed inside the block elide transfers for these names.
        ``nowait`` regions launched inside must be joined (``drain`` /
        ``taskwait``) before the block exits.
        """
        self.enter_data(device, "target_data", **values)
        try:
            yield self
        finally:
            self.exit_data(device, *values.keys())

    def fetch_resident(self, device: int, name: str) -> Any:
        """Pull a resident buffer's device copy back to the host.

        The read side of ``device_out`` maps: after on-device updates the
        entry is *device-ahead*; this fetches every leaf, records the
        fetched values as the entry's host view (so host-value matches work
        again) and clears the flag.
        """
        pool = self.pool
        with pool.env_locks[device]:
            ent = pool.present[device].get(name)
            if ent is None:
                raise KeyError(f"{name!r} is not resident on device {device}")
            if ent.spilled:
                # the device copy was evicted after reconciliation: the host
                # view IS the value — no device traffic, entry stays spilled
                leaves = [jnp.asarray(l) for l in ent.host_leaves]
                return (leaves[0] if ent.treedef is None
                        else jax.tree.unflatten(ent.treedef, leaves))
            # a failed writer means the device copy is garbage: re-send from
            # the host view, or surface the stored DeviceFailure so graph
            # recovery replays the producer
            self._heal_locked(device, ent, f"fetch:{name}")
            ent.refcount += 1          # hold the entry: a concurrent
                                       # exit_data must not free (and first-
                                       # fit-recycle) the handles mid-fetch
            handles, treedef = list(ent.handles), ent.treedef
            seen = (ent.version, tuple(ent.write_futs))
        try:
            fetched = [pool.transfer_from(device, h, tag=f"fetch:{name}")
                       for h in handles]
            with pool.env_locks[device]:
                ent = pool.present[device].get(name)
                # reconcile only if nothing wrote the entry while we fetched —
                # a concurrent region's device_out advance (new write_futs /
                # version) must not be clobbered with our pre-advance snapshot
                if (ent is not None and len(ent.host_leaves) == len(fetched)
                        and (ent.version, tuple(ent.write_futs)) == seen):
                    ent.host_leaves = list(fetched)
                    ent.device_ahead = False
        finally:
            self.exit_data(device, name)
        return fetched[0] if treedef is None else jax.tree.unflatten(treedef, fetched)

    # -- region lifecycle (paper §4.1/§4.2) ------------------------------------
    def _run(self, kernel: str, device: int, maps: MapSpec, tag: str,
             work: Optional[Dict[str, float]] = None) -> Dict[str, jax.Array]:
        with span(REGION, device=device, kernel=kernel, tag=tag):
            return self._region(kernel, device, maps, tag, work)

    def _region(self, kernel: str, device: int, maps: MapSpec, tag: str,
                work: Optional[Dict[str, float]] = None) -> Dict[str, jax.Array]:
        pool = self.pool
        handles: Dict[str, Any] = {}   # name -> handle | [handles] (pytree)
        trees: Dict[str, Any] = {}     # name -> treedef for pytree maps
        owned: List[int] = []    # region-lifetime handles, freed at region end
        retained: List[str] = []  # present-table names released at region end
        # matched present entries are consumed through a StreamTicket: opened
        # under the env lock at match time, closed right after EXEC.  The
        # ticket's deps order our EXEC after the content's producers; the
        # open registration orders any later writer (a concurrent region's
        # refresh) after our EXEC — per-handle producer/consumer ordering
        # instead of serializing whole regions.
        tickets: Dict[str, StreamTicket] = {}
        ticketed: set = set()          # handles covered by an open ticket
        exec_deps: List[Any] = []

        def _retain_ticketed(name: str, ent: PresentEntry) -> List[int]:
            self._heal_locked(device, ent, tag or name)
            hs = list(ent.handles)
            retained.append(name)
            if name not in tickets:    # same name in two clauses reuses the
                                       # ticket — overwriting would leak an
                                       # open reader and wedge later writers
                t = pool.open_reader(device, hs)
                tickets[name] = t
                exec_deps.extend(t.deps)
            ticketed.update(hs)
            exec_deps.extend(f for f in ent.write_futs if f is not None)
            return hs

        # The try spans setup too: a failure after a present-table retain or
        # an ALLOC must still release/free in the teardown below.
        try:
            # 0) present/device_out names bind the resident handles directly;
            #    no host value travels, so they work on device-ahead entries.
            present_alias = _alias_map(maps.present)
            out_alias = _alias_map(maps.device_out)
            for kwarg, rname in {**present_alias, **out_alias}.items():
                with pool.env_locks[device]:
                    ent = pool.present[device].get(rname)
                    if ent is None:
                        raise KeyError(
                            f"map(present) name {rname!r} is not resident on "
                            f"device {device}; enter_data/ensure_resident it first")
                    if ent.spilled:
                        # a present binding REQUIRES residency: transparently
                        # refetch the evicted content before binding handles
                        self._refetch_locked(device, ent, tag or "present")
                    ent.refcount += 1
                    pool.present[device].touch(ent)
                    hs = _retain_ticketed(rname, ent)
                    treedef = ent.treedef
                handles[kwarg] = hs[0] if treedef is None else hs
                if treedef is not None:
                    trees[kwarg] = treedef
            # 1) ALLOC + XFER_TO for to/tofrom — unless the name is present on
            #    the device with the same host value, in which case the
            #    transfer is elided and the resident handles used directly.
            for name, val in {**maps.to, **maps.tofrom}.items():
                leaves, treedef = _flatten_map_value(val)
                ent = None
                if not any(isinstance(l, Section) for l in leaves):
                    with pool.env_locks[device]:
                        self._maybe_revive_value(device, name, leaves,
                                                 treedef, tag or name)
                        ent = pool.present[device].match_value(name, leaves, treedef)
                        if ent is not None:
                            hs = _retain_ticketed(name, ent)
                if ent is None:
                    hs = []
                    for leaf in leaves:
                        v = leaf.value if isinstance(leaf, Section) else host_array(leaf)
                        h = pool.alloc(device, v.shape, v.dtype, tag=f"{tag}:{name}")
                        # the send is a dep of our EXEC: the post-EXEC check
                        # below must see ITS failure, not let it surface (and
                        # be absorbed) at some other region's sync point
                        # while this kernel's garbage result stands
                        exec_deps.append(
                            pool.transfer_to(device, h, v, tag=f"{tag}:{name}"))
                        hs.append(h)
                        owned.append(h)
                handles[name] = hs[0] if treedef is None else hs
                if treedef is not None:
                    trees[name] = treedef
            # ALLOC only for alloc/from_ — a present entry of matching shape
            # is reused as the output buffer (resident results stay on-device).
            for name, spec in {**maps.alloc, **maps.from_}.items():
                leaves, treedef = _flatten_map_value(spec)
                specs = [_as_spec(leaf) for leaf in leaves]
                with pool.env_locks[device]:
                    self._maybe_revive_specs(device, name, specs, treedef,
                                             tag or name)
                    ent = pool.present[device].match_specs(name, specs, treedef)
                    if ent is not None:
                        hs = _retain_ticketed(name, ent)
                if ent is None:
                    hs = []
                    for s in specs:
                        h = pool.alloc(device, s.shape, s.dtype, tag=f"{tag}:{name}")
                        hs.append(h)
                        owned.append(h)
                handles[name] = hs[0] if treedef is None else hs
                if treedef is not None:
                    trees[name] = treedef
            for name in maps.use_globals:
                handles[name] = pool.globals[name][device]

            # 2) EXEC — kernel sees device-resident buffers as kwargs, returns
            #    replacements for from_/tofrom/device_out names.  Ticketed
            #    handles must not re-register as readers (a writer queued
            #    behind our ticket would deadlock the EXEC): their ordering
            #    travels in extra_deps.
            result = pool.exec_kernel(device, kernel, buffers=handles, trees=trees,
                                      firstprivate=maps.firstprivate, tag=tag,
                                      skip_reads=tuple(ticketed),
                                      extra_deps=tuple(exec_deps), work=work)
            # the EXEC was *ordered* after its deps, not gated on their
            # success: a dep that failed between retain and EXEC left its
            # buffer unwritten, so the kernel just computed on garbage —
            # surface the dep's error instead of returning the result.  All
            # deps are settled here (the EXEC ran), so this never blocks.
            for f in exec_deps:
                if f is not None and f.done() and f.exception() is not None:
                    raise f.exception()
            returned: Dict[str, Any] = {}
            if result is not None:
                if not isinstance(result, Mapping):
                    raise TypeError(
                        f"kernel {kernel!r} must return a dict of mapped outputs, "
                        f"got {type(result)}")
                returned = dict(result)

            # the EXEC has consumed the matched content: release the reader
            # registrations so writers (our own write-backs, other regions'
            # refreshes) may proceed.
            for t in tickets.values():
                t.close()

            def _ret_leaves(name: str) -> Tuple[List[int], List[Any], Any]:
                if name not in returned:
                    raise KeyError(f"kernel {kernel!r} did not return mapped output {name!r}")
                h = handles[name]
                hs = h if isinstance(h, list) else [h]
                ret_leaves, ret_def = jax.tree.flatten(returned[name])
                if len(ret_leaves) != len(hs):
                    raise ValueError(
                        f"kernel {kernel!r} returned {len(ret_leaves)} leaves "
                        f"for {name!r}, mapped {len(hs)}")
                return hs, ret_leaves, ret_def

            def _writeback_ahead(rname: str, hs: List[int], ret_leaves: List[Any],
                                 bump_version: bool) -> Optional[Tuple[int, Tuple]]:
                """Mark the entry device-ahead and submit the writebacks in
                ONE env-lock critical section: a concurrent match must
                either see device_ahead (and miss) or run entirely before
                the writeback is even queued — never elide the stale host
                value yet be stream-ordered after the new content.  Returns
                a (version, write_futs) snapshot for the reconcile guard."""
                with pool.env_locks[device]:
                    ent = pool.present[device].get(rname)
                    if ent is not None:
                        ent.device_ahead = True
                        if bump_version:
                            ent.version += 1
                    wfuts = [pool.transfer_to_writeback(device, hh, leaf)
                             for hh, leaf in zip(hs, ret_leaves)]
                    if ent is None:
                        return None
                    ent.write_futs = wfuts
                    return (ent.version, tuple(wfuts))

            # 3a) device_out: write back on-device, mark the entry ahead of
            #     the host, move NOTHING over the wire.
            for kwarg, rname in out_alias.items():
                hs, ret_leaves, _ = _ret_leaves(kwarg)
                _writeback_ahead(rname, hs, ret_leaves, bump_version=True)

            # 3b) write-back + XFER_FROM for from_/tofrom.
            out: Dict[str, jax.Array] = {}
            for name in list(maps.from_) + list(maps.tofrom):
                hs, ret_leaves, ret_def = _ret_leaves(name)
                fetched: List[Any] = []
                if name in retained:
                    # resident output: device-ahead until the fetch below
                    # reconciles the entry with the fetched host value
                    seen = _writeback_ahead(name, hs, ret_leaves,
                                            bump_version=False)
                    for hh in hs:
                        fetched.append(pool.transfer_from(device, hh,
                                                          tag=f"{tag}:{name}"))
                    with pool.env_locks[device]:
                        ent = pool.present[device].get(name)
                        # same guard as fetch_resident: only reconcile if no
                        # concurrent region advanced the entry meanwhile
                        if (ent is not None and seen is not None
                                and len(ent.host_leaves) == len(fetched)
                                and (ent.version, tuple(ent.write_futs)) == seen):
                            # record the fetched host value so a later
                            # map(to) of it elides
                            ent.host_leaves = list(fetched)
                            ent.version += 1
                            ent.device_ahead = False
                else:
                    for hh, leaf in zip(hs, ret_leaves):
                        pool.transfer_to_writeback(device, hh, leaf)
                        fetched.append(pool.transfer_from(device, hh,
                                                          tag=f"{tag}:{name}"))
                out[name] = (fetched[0] if not isinstance(handles[name], list)
                             else jax.tree.unflatten(ret_def, fetched))
            return out
        finally:
            # 4) region end: free region-lifetime handles on both device and
            #    host mirror (paper: "allocated variables are freed from the
            #    device's mediary address array and their positions are marked
            #    as unused") and settle the device queue so a resolved region
            #    future implies the device reached the same state.  Present
            #    entries only drop the region's reference — data stays
            #    resident until its data environment exits.
            for t in tickets.values():
                t.close()              # idempotent; vital on the error path —
                                       # an open ticket would wedge every
                                       # later writer of those handles
            try:
                for h in owned:
                    pool.free(device, h)
                if owned:
                    pool.sync(device)
                if retained:
                    self.exit_data(device, *retained)
            except DeviceStoppedError:
                pass                       # device stopped mid-teardown:
                                           # nothing left to free; any other
                                           # error (incl. stashed async device
                                           # errors) must surface
