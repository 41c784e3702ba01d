"""Mediary addresses: host↔device buffer-handle indirection (paper §4.2).

The host cannot know remote virtual addresses, so OMPi maps a host address to
an abstract *mediary address* — here, an integer slot in a per-device dynamic
array.  The device stores the real buffer at that slot; the host keeps a
*mirror* of the array (marking reserved slots with the sentinel ``0x999``) so
it can assign the next handle without a network round trip.

JAX adaptation: the "real buffer" is a ``jax.Array`` placed on the device's
sharding; the host mirror stores only ``ShapeDtypeStruct`` metadata (zero
allocation — the paper: "the host does not need to allocate any memory, it
only needs to remember which elements are in use").  Global variables (paper:
``declare target``) are installed at slot-table construction time, in the same
deterministic order on host and device.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Paper §4.2: "marks it with the special (and arbitrary) value of 0x999".
RESERVED = 0x999


def same_treedef(a: Any, b: Any) -> bool:
    """None-safe treedef equality (PyTreeDef.__eq__ rejects None operands)."""
    if (a is None) != (b is None):
        return False
    return a is None or a == b


class SlotTableBase:
    """First-fit slot allocator shared by device store and host mirror."""

    def __init__(self) -> None:
        self._slots: List[Any] = []  # None = unused (paper: NULL address)

    def _first_free(self) -> int:
        for i, v in enumerate(self._slots):
            if v is None:
                return i
        self._slots.append(None)
        return len(self._slots) - 1

    def free(self, handle: int) -> None:
        if not (0 <= handle < len(self._slots)) or self._slots[handle] is None:
            raise KeyError(f"mediary handle {handle} is not live")
        self._slots[handle] = None

    def live_handles(self) -> List[int]:
        return [i for i, v in enumerate(self._slots) if v is not None]

    def __len__(self) -> int:
        return len(self._slots)


@dataclass(frozen=True)
class _Calloc:
    """A slot ALLOC reserved and nothing has written yet.

    It reads as zeros (OMPi allocates with ``calloc()``), but the zeros are
    made only if the slot is read before it is written: the usual ALLOC is
    followed by a whole-buffer transfer, and zero-filling first would hold
    a second buffer of the same size on the device until that transfer ran.
    """
    shape: Tuple[int, ...]
    dtype: Any


class MediaryStore(SlotTableBase):
    """Device-side mediary array: handle → actual buffer (paper: calloc'd ptr)."""

    def __init__(self, sharding: Optional[jax.sharding.Sharding] = None) -> None:
        super().__init__()
        self._sharding = sharding
        self.zero_fills = 0      # unwritten slots made zeros by a read

    # -- commands from the host (paper §4.1 command types) -----------------
    def alloc(self, shape: Sequence[int], dtype: Any) -> int:
        """ALLOC: zero-initialized, as OMPi uses ``calloc()``."""
        handle = self._first_free()
        self._slots[handle] = _Calloc(tuple(shape),
                                      jax.dtypes.canonicalize_dtype(dtype))
        return handle

    def install(self, handle: int, value: jax.Array) -> None:
        """Place an existing array at a specific slot (global-variable setup)."""
        while len(self._slots) <= handle:
            self._slots.append(None)
        if self._slots[handle] is not None:
            raise KeyError(f"mediary handle {handle} already live")
        self._slots[handle] = value

    def write(self, handle: int, value: jax.Array, section: Optional[slice] = None) -> None:
        """TRANSFER_TO: host → device (optionally into an array section)."""
        cur = self._live(handle)
        value = jnp.asarray(value, dtype=cur.dtype)
        if section is not None:
            cur = self._lookup(handle).at[section].set(value)
        else:
            if value.shape != cur.shape:
                raise ValueError(f"shape mismatch {value.shape} vs {cur.shape}")
            cur = value
        if self._sharding is not None:
            cur = jax.device_put(cur, self._sharding)
        self._slots[handle] = cur

    def read(self, handle: int, section: Optional[slice] = None) -> jax.Array:
        """TRANSFER_FROM: device → host."""
        cur = self._lookup(handle)
        return cur[section] if section is not None else cur

    def _live(self, handle: int) -> Any:
        if not (0 <= handle < len(self._slots)) or self._slots[handle] is None:
            raise KeyError(f"mediary handle {handle} is not live")
        return self._slots[handle]

    def _lookup(self, handle: int) -> jax.Array:
        cur = self._live(handle)
        if isinstance(cur, _Calloc):     # first read of an unwritten slot
            cur = self._slots[handle] = jnp.zeros(cur.shape, cur.dtype,
                                                  device=self._sharding)
            self.zero_fills += 1
        return cur

    # Device addresses (paper fig. 1 right column) — for tracing/debugging.
    def device_address(self, handle: int):
        return self._lookup(handle)


@dataclass(frozen=True)
class MirrorEntry:
    spec: jax.ShapeDtypeStruct
    nbytes: int


# ---------------------------------------------------------------------------
# Present table: persistent device data environments (OpenMP target data)
# ---------------------------------------------------------------------------
@dataclass
class PresentEntry:
    """One logical buffer resident on a device.

    ``host_leaves`` are the host-side array objects last sent (identity is
    the change detector: JAX arrays are immutable, so a new value is a new
    object).  ``version`` bumps on every re-send, letting callers observe
    that a host update actually crossed the wire.
    """

    name: str
    handles: List[int]
    treedef: Any                       # None = single array (not a pytree)
    host_leaves: List[Any]
    specs: List[jax.ShapeDtypeStruct]
    refcount: int = 1
    version: int = 0
    # bytes sent by the enter/refresh that produced the current content —
    # the first elision hit consumes this debit so "bytes elided" reports
    # net savings vs a per-region baseline, not gross region elisions
    debit: int = 0
    # per-leaf future of the last command that wrote the device copy (the
    # enter/refresh XFER_TO or a device_out writeback).  A consumer that
    # matched this entry orders its EXEC after these; the stream's
    # write-after-read tracking orders the *next* writer after the EXEC.
    write_futs: List[Any] = field(default_factory=list)
    # the device copy has advanced past host_leaves (a ``device_out`` map
    # wrote it on-device and nothing fetched it yet); host-value matches
    # must miss until fetch_resident or a refresh reconciles the two sides
    device_ahead: bool = False
    # capacity eviction spilled the device copy: ``handles`` are empty, the
    # authoritative value lives in ``host_leaves`` (device-ahead entries are
    # reconciled to the host before their buffers are freed), and the next
    # present-binding refetches transparently.  A spilled entry holds zero
    # device memory but keeps its logical identity and references.
    spilled: bool = False
    # LRU clock stamp (PresentTable._clock at last touch)
    last_used: int = 0
    # pinned entries are never eviction candidates, whatever their refcount
    pinned: bool = False

    def nbytes(self) -> int:
        return sum(int(np.prod(s.shape, dtype=np.int64)) * jnp.dtype(s.dtype).itemsize
                   for s in self.specs)

    def peer_clone(self, handles: List[int], write_futs: List[Any]) -> "PresentEntry":
        """A copy of this entry fulfilled on *another* device, device→device.

        The clone inherits this entry's logical identity (name, structure,
        host view) but binds the peer's mediary ``handles``, with
        ``write_futs`` the RECV futures that are filling them.  Crucially a
        *device-ahead* entry propagates as device-ahead: the peer's copy is
        as far past the host as the source's, and no host reconciliation
        (fetch + re-send) happens on the way — the host-side ``host_leaves``
        snapshot travels along only so that a later host-value match behaves
        identically on both devices.
        """
        return PresentEntry(
            name=self.name, handles=list(handles), treedef=self.treedef,
            host_leaves=list(self.host_leaves), specs=list(self.specs),
            refcount=1, version=self.version, debit=0,
            write_futs=list(write_futs), device_ahead=self.device_ahead)


class PresentTable:
    """Reference-counted name → device-buffer map (OpenMP's present table).

    OpenMP keeps a per-device table of host ranges already mapped; a map
    clause whose variable is *present* skips allocation and transfer and
    only adjusts the reference count.  Ours is keyed by the logical buffer
    name in the :class:`~repro.core.target.MapSpec` and additionally tracks
    content versions so stale device copies are refreshed exactly when the
    host value changed.  Synchronization is the owner's job (the pool holds
    one data-environment lock per device).

    ``capacity_bytes`` (None = unbounded) caps the *resident* device memory
    this table may hold.  The table itself never moves bytes — eviction is
    driven by :meth:`~repro.core.target.TargetExecutor` through
    :meth:`lru_victim`: the least-recently-used entry that is neither pinned
    nor retained by an in-flight region (refcount > 1 means a region holds
    it through an open stream ticket) is *spilled* — device buffers freed,
    logical entry kept — and transparently refetched on its next binding.
    """

    def __init__(self, capacity_bytes: Optional[int] = None) -> None:
        self._entries: Dict[str, PresentEntry] = {}
        self.capacity_bytes = capacity_bytes
        # observability: how much traffic the table elided
        self.hits = 0
        self.misses = 0
        self.bytes_elided = 0
        # capacity/eviction observability
        self.evictions = 0
        self.refetches = 0
        self.bytes_reconciled = 0     # device-ahead content fetched at spill
        self.bytes_refetched = 0      # spilled content re-sent at next bind
        self._clock = 0               # LRU stamp source

    def get(self, name: str) -> Optional[PresentEntry]:
        return self._entries.get(name)

    def add(self, entry: PresentEntry) -> None:
        if entry.name in self._entries:
            raise KeyError(f"{entry.name!r} already present")
        self.touch(entry)
        self._entries[entry.name] = entry

    def touch(self, entry_or_name) -> None:
        """Stamp an entry as most-recently-used (LRU bookkeeping)."""
        e = (self._entries.get(entry_or_name)
             if isinstance(entry_or_name, str) else entry_or_name)
        if e is not None:
            self._clock += 1
            e.last_used = self._clock

    def used_bytes(self) -> int:
        """Device bytes currently held by resident (non-spilled) entries."""
        return sum(e.nbytes() for e in self._entries.values() if not e.spilled)

    def lru_victim(self, protect: Sequence[str] = ()) -> Optional[PresentEntry]:
        """Least-recently-used evictable entry, or None.

        Evictable: not pinned, not already spilled, not named in ``protect``,
        and refcount <= 1 — a refcount above the owner's single reference
        means an in-flight region retains the entry (its handles may be
        covered by an open stream ticket), so it is skipped.
        """
        best: Optional[PresentEntry] = None
        for e in self._entries.values():
            if (e.pinned or e.spilled or e.refcount > 1
                    or e.name in protect):
                continue
            if best is None or e.last_used < best.last_used:
                best = e
        return best

    def names(self) -> List[str]:
        return sorted(self._entries)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def match_value(self, name: str, leaves: Sequence[Any],
                    treedef: Any) -> Optional[PresentEntry]:
        """Entry iff ``name`` is present with the *same* host value.

        Identity per leaf is the test, and only immutable ``jax.Array``
        leaves are elidable — a mutable host array (numpy) could be updated
        in place without changing identity, which would silently serve a
        stale device copy.  A hit means zero bytes need to move.  Retains
        the entry (refcount++); pair with :meth:`release`.
        """
        e = self._entries.get(name)
        if (e is None or e.device_ahead or e.spilled
                or not same_treedef(e.treedef, treedef)
                or len(e.host_leaves) != len(leaves)
                or any(a is not b or not isinstance(b, jax.Array)
                       for a, b in zip(e.host_leaves, leaves))):
            # absent, present-but-stale and spilled all miss — the TABLE
            # holds no device buffers for a spilled entry; the executor
            # revives a would-match entry (transparent refetch) BEFORE
            # consulting the table, so callers see a hit again
            self.misses += 1
            return None
        e.refcount += 1
        self.hits += 1
        self.touch(e)
        self.bytes_elided += max(0, e.nbytes() - e.debit)
        e.debit = 0
        return e

    def match_specs(self, name: str, specs: Sequence[jax.ShapeDtypeStruct],
                    treedef: Any) -> Optional[PresentEntry]:
        """Entry iff ``name`` is present with matching shapes/dtypes.

        Used for output (``from``/``alloc``) maps where no host value exists
        yet: the resident buffer is reused in place of a fresh allocation.
        Retains the entry on success.
        """
        e = self._entries.get(name)
        if (e is None or e.spilled
                or not same_treedef(e.treedef, treedef)
                or len(e.specs) != len(specs)
                or any(a.shape != b.shape or jnp.dtype(a.dtype) != jnp.dtype(b.dtype)
                       for a, b in zip(e.specs, specs))):
            return None
        e.refcount += 1
        self.hits += 1
        self.touch(e)
        return e

    def pop_entry(self, name: str) -> Optional[PresentEntry]:
        """Remove and return an entry without touching refcounts or buffers.

        Used by elastic rescale to *relocate* a (spilled) logical entry to a
        surviving device's table; the caller owns the device-buffer
        lifecycle on both sides.
        """
        return self._entries.pop(name, None)

    def adopt(self, entry: PresentEntry) -> bool:
        """Install a relocated entry; False (no-op) if the name is taken.

        The adopting table keeps its own copy on a name clash — the survivor
        was reachable all along, the migrant was not.
        """
        if entry.name in self._entries:
            return False
        self.touch(entry)
        self._entries[entry.name] = entry
        return True

    def release(self, name: str) -> Optional[PresentEntry]:
        """Refcount--; returns the now-dead entry (caller frees) or None."""
        e = self._entries.get(name)
        if e is None:
            return None
        e.refcount -= 1
        if e.refcount <= 0:
            del self._entries[name]
            return e
        return None

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "bytes_elided": self.bytes_elided,
                "resident": len(self._entries),
                "resident_bytes": self.used_bytes(),
                "capacity_bytes": (-1 if self.capacity_bytes is None
                                   else self.capacity_bytes),
                "spilled": sum(1 for e in self._entries.values() if e.spilled),
                "evictions": self.evictions, "refetches": self.refetches,
                "bytes_reconciled": self.bytes_reconciled,
                "bytes_refetched": self.bytes_refetched}


class HostMirror(SlotTableBase):
    """Host-side mirror (paper §4.2 optimization): predicts handles, holds no data.

    ``reserve()`` returns the handle the device *will* use for its next alloc,
    marking the slot with ``RESERVED`` semantics; the runtime then issues the
    actual ALLOC command.  Because both sides run first-fit over identical
    op sequences, handles always agree (property-tested).
    """

    def reserve(self, shape: Sequence[int], dtype: Any) -> int:
        handle = self._first_free()
        spec = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        nbytes = int(np.prod(spec.shape, dtype=np.int64)) * spec.dtype.itemsize
        # The slot value *is* the 0x999 marker until the device confirms; we
        # keep the spec alongside so transfers can be size-checked host-side.
        self._slots[handle] = MirrorEntry(spec=spec, nbytes=nbytes)
        return handle

    def install(self, handle: int, spec: jax.ShapeDtypeStruct) -> None:
        while len(self._slots) <= handle:
            self._slots.append(None)
        if self._slots[handle] is not None:
            raise KeyError(f"mirror handle {handle} already live")
        nbytes = int(np.prod(spec.shape, dtype=np.int64)) * jnp.dtype(spec.dtype).itemsize
        self._slots[handle] = MirrorEntry(spec=spec, nbytes=nbytes)

    def spec(self, handle: int) -> jax.ShapeDtypeStruct:
        entry = self._slots[handle]
        if entry is None:
            raise KeyError(f"mirror handle {handle} is not live")
        return entry.spec

    def nbytes(self, handle: int) -> int:
        entry = self._slots[handle]
        if entry is None:
            raise KeyError(f"mirror handle {handle} is not live")
        return entry.nbytes
