"""Logical-axis sharding rules → NamedSharding, divisibility-aware.

Models annotate tensors with *logical* axis names (``batch``, ``seq``,
``embed``, ``heads``, ``kv``, ``ff``, ``expert``, ``vocab``, ``state``,
``layers``, ...).  An :class:`AxisRules` table maps logical names to mesh
axes; :func:`logical_constraint` resolves the annotation inside traced code
via ``jax.lax.with_sharding_constraint``.

Divisibility fallback: a rule only applies if the dimension size is divisible
by the mesh-axis size (product, for tuple targets); otherwise the dimension is
replicated.  This is what lets one rules table compile every assigned
arch × mesh cell (e.g. gemma3's 8 heads cannot split over a 16-way ``model``
axis — its head axis silently falls back to replicated while ``ff``/``vocab``
still shard).

Activated as a context (``with axis_rules(rules, mesh): ...``) so model code
stays mesh-agnostic and single-device smoke tests run with no rules at all.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class AxisRules:
    """Ordered logical-name → mesh-axes table."""

    rules: Tuple[Tuple[str, MeshAxes], ...]

    @classmethod
    def of(cls, **kw: MeshAxes) -> "AxisRules":
        return cls(tuple(kw.items()))

    def lookup(self, name: str) -> MeshAxes:
        for k, v in self.rules:
            if k == name:
                return v
        return None

    def replace(self, **kw: MeshAxes) -> "AxisRules":
        d = dict(self.rules)
        d.update(kw)
        return AxisRules(tuple(d.items()))


_CTX: contextvars.ContextVar[Optional[Tuple[AxisRules, Mesh]]] = \
    contextvars.ContextVar("axis_rules", default=None)


@contextlib.contextmanager
def axis_rules(rules: AxisRules, mesh: Mesh):
    token = _CTX.set((rules, mesh))
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _CTX.reset(token)


def current_rules() -> Optional[Tuple[AxisRules, Mesh]]:
    return _CTX.get()


def _axes_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def spec_for(shape: Sequence[int], names: Sequence[Optional[str]],
             rules: AxisRules, mesh: Mesh) -> P:
    """Resolve logical names to a PartitionSpec, dropping non-divisible axes.

    A mesh axis may appear at most once in a PartitionSpec; first (leftmost)
    logical dim wins, later claims fall back to replicated.
    """
    assert len(shape) == len(names), (shape, names)
    used: set = set()
    out = []
    for dim, name in zip(shape, names):
        axes = rules.lookup(name) if name else None
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        tup = tuple(a for a in tup if a in mesh.shape)
        if not tup or any(a in used for a in tup):
            out.append(None)
            continue
        if dim % _axes_size(mesh, tup) != 0:
            out.append(None)                      # divisibility fallback
            continue
        used.update(tup)
        out.append(tup[0] if len(tup) == 1 else tup)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_sharding(shape: Sequence[int], names: Sequence[Optional[str]],
                     rules: Optional[AxisRules] = None,
                     mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    if rules is None or mesh is None:
        ctx = current_rules()
        if ctx is None:
            return None
        rules, mesh = ctx
    return NamedSharding(mesh, spec_for(shape, names, rules, mesh))


def logical_constraint(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Annotate a traced array with logical axes; no-op outside axis_rules."""
    ctx = current_rules()
    if ctx is None:
        return x
    rules, mesh = ctx
    sh = logical_sharding(x.shape, names, rules, mesh)
    return jax.lax.with_sharding_constraint(x, sh)


# ---------------------------------------------------------------------------
# Parameter sharding: walk a params pytree with a logical-name tree
# ---------------------------------------------------------------------------
def shard_params_like(params_shapes: Any, names_tree: Any, rules: AxisRules,
                      mesh: Mesh) -> Any:
    """Build a NamedSharding pytree for ``params_shapes``.

    ``names_tree`` mirrors the params tree; each leaf is a tuple of logical
    names (len == rank of the corresponding param).  Missing names → replicated.
    """
    def one(shape_leaf, names):
        if names is None:
            return NamedSharding(mesh, P())
        return logical_sharding(shape_leaf.shape, names, rules, mesh)

    return jax.tree.map(one, params_shapes, names_tree,
                        is_leaf=lambda x: hasattr(x, "shape"))
