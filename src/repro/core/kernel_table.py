"""Kernel table: stable integer identifiers for offloadable kernels.

Paper §4.1: remote processes are replicas of the host executable, so function
*pointers* differ across nodes but registration *order* does not.  Every node
builds a ``kerneltable`` mapping each kernel function to a unique integer, and
the host offloads by sending the integer index.

JAX/TPU adaptation: in SPMD multi-controller JAX every process runs the same
program, which is exactly the property the paper exploits.  We keep the stable
integer index and add a TPU-native dispatch path: ``lax.switch`` over all
registered kernels of a *signature class*, so a single compiled device program
can execute a heterogeneous command stream addressed by table index (the
device-side command loop of paper §4.1, expressed as traced control flow).
"""
from __future__ import annotations

import functools
import hashlib
import inspect
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import jax


@dataclass(frozen=True)
class KernelEntry:
    """One row of the kerneltable (paper: {name, code pointer})."""

    index: int
    name: str
    fn: Callable
    signature: Optional[str] = None  # signature class for lax.switch dispatch
    # zero-arg callable returning example operands (positional tuple or
    # kwargs dict) — lets the calibration pass micro-benchmark the kernel
    # without the caller supplying operand shapes.  Excluded from
    # fingerprint(): it is measurement metadata, not dispatch identity.
    example: Optional[Callable] = None
    # ``fn`` under the entry's name: jit names its XLA module after the
    # function it wraps, so a device trace reads ``jit_<name>`` whether
    # ``fn`` is a lambda or a function of another name.  One per entry, so
    # every device's jit wrapper shares one trace.
    named_fn: Callable = field(init=False, repr=False, compare=False)
    # the mapped names ``fn`` takes, read once at registration; None when
    # it takes ``**kwargs`` (every name).  An EXEC resolves only these.
    params: Optional[FrozenSet[str]] = field(init=False, repr=False,
                                             compare=False)

    def __post_init__(self) -> None:
        object.__setattr__(self, "named_fn", _renamed(self.fn, self.name))
        object.__setattr__(self, "params", _params(self.fn))


def _params(fn: Callable) -> Optional[FrozenSet[str]]:
    """The names ``fn`` takes as keywords, or None if it takes ``**kwargs``."""
    params = inspect.signature(fn).parameters
    if any(p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return None
    return frozenset(params)


def _renamed(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, keeping its signature (``__wrapped__``)."""

    @functools.wraps(fn)
    def kernel(*args, **kwargs):
        return fn(*args, **kwargs)

    kernel.__name__ = kernel.__qualname__ = name
    return kernel


class KernelTable:
    """Deterministic-order kernel registry (paper §4.1 ``kerneltable``).

    Registration order defines the index; as in the paper, every process must
    register the same kernels in the same order ("functions are entered in each
    kerneltable in the exact same order; as a result, each function is mapped
    to the same unique integer across all nodes").  ``fingerprint()`` lets a
    runtime *verify* that property instead of assuming it.
    """

    def __init__(self) -> None:
        self._entries: List[KernelEntry] = []
        self._by_name: Dict[str, KernelEntry] = {}

    # -- registration -----------------------------------------------------
    def register(self, name: str, fn: Callable, *,
                 signature: Optional[str] = None,
                 example: Optional[Callable] = None) -> int:
        if name in self._by_name:
            raise ValueError(f"kernel {name!r} already registered")
        entry = KernelEntry(index=len(self._entries), name=name, fn=fn,
                            signature=signature, example=example)
        self._entries.append(entry)
        self._by_name[name] = entry
        return entry.index

    def kernel(self, name: Optional[str] = None, *,
               signature: Optional[str] = None,
               example: Optional[Callable] = None):
        """Decorator: ``@table.kernel()`` — the 'outlining' step of paper §4."""

        def deco(fn: Callable) -> Callable:
            self.register(name or fn.__name__, fn, signature=signature,
                          example=example)
            return fn

        return deco

    # -- lookup -----------------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def index_of(self, name: str) -> int:
        """Host side of an offload: name → wire index (paper: array index)."""
        return self._by_name[name].index

    def lookup(self, index: int) -> KernelEntry:
        """Device side: wire index → local function pointer."""
        return self._entries[index]

    def names(self) -> List[str]:
        return [e.name for e in self._entries]

    def fingerprint(self) -> str:
        """Digest of (index, name) pairs; all nodes must agree before EXEC."""
        h = hashlib.sha256()
        for e in self._entries:
            h.update(f"{e.index}:{e.name};".encode())
        return h.hexdigest()[:16]

    # -- TPU-native dispatch ------------------------------------------------
    def switch_dispatch(self, signature: str) -> Callable:
        """Build a traced dispatcher over all kernels of one signature class.

        Returns ``dispatch(kernel_id, *operands)`` where ``kernel_id`` is a
        traced int32 scalar — the device-side command loop of paper §4.1 as
        ``jax.lax.switch``.  All kernels in a signature class must share an
        (operands → outputs) shape contract; the sub-table index used on the
        wire is the position within the class, obtained from
        ``class_index_of``.
        """
        branches = [e.fn for e in self._entries if e.signature == signature]
        if not branches:
            raise ValueError(f"no kernels with signature {signature!r}")

        def dispatch(kernel_id, *operands):
            return jax.lax.switch(kernel_id, branches, *operands)

        return dispatch

    def class_index_of(self, name: str) -> int:
        """Index of ``name`` within its signature class (for switch_dispatch)."""
        entry = self._by_name[name]
        peers = [e for e in self._entries if e.signature == entry.signature]
        return next(i for i, e in enumerate(peers) if e.name == name)


# The process-global table, mirroring the paper's per-executable kerneltable.
GLOBAL_KERNEL_TABLE = KernelTable()


def kernel(name: Optional[str] = None, *, signature: Optional[str] = None):
    """Module-level decorator registering into the global kerneltable."""
    return GLOBAL_KERNEL_TABLE.kernel(name, signature=signature)
