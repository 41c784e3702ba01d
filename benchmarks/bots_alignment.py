"""Paper Figs 2–3, BOTS ``alignment``: ClustalW's pairwise scores of all pairs.

BOTS ``alignment`` is the pairwise stage of ClustalW: every pair ``i < j``
of a bank of protein sequences is aligned, each pair independent of every
other ("embarrassingly parallel, tiny comm").  Per pair, BOTS runs
ClustalW's ``forward_pass``: a local alignment with affine gap costs.  For
sequences ``a`` (length m) and ``b`` (length n), substitution matrix ``S``
(in ClustalW's integer units, :data:`INT_SCALE` per matrix unit), gap open
``g`` and gap extend ``gh``::

    H[i][0] = H[0][j] = 0,  E and F start at minus infinity
    E[i][j] = max(E[i][j-1], H[i][j-1] - g) - gh
    F[i][j] = max(F[i-1][j], H[i-1][j] - g) - gh
    H[i][j] = max(H[i-1][j-1] + S[a_i][b_j], E[i][j], F[i][j], 0)

and the pair's score is the largest ``H[i][j]`` over ``1 <= i <= m``,
``1 <= j <= n``: a gap of length k costs ``g + k*gh``.  The gap costs are
ClustalW's pairwise ones (``pairalign``): ``gh = INT_SCALE * pw_ge_penalty``
and, per pair, ``g = 2 * mat_avscore * (pw_go_penalty + ln(min(m, n)))``,
where ``mat_avscore`` is minus the mean off-diagonal entry of the scaled
matrix (``2 * INT_SCALE * (...)`` where that is not positive).  BOTS goes
on from the cell of the largest score to a reverse pass and a Myers-Miller
alignment for a percent identity; this module computes the forward pass.

The bank and the plan of its pairs are invariant and sent once to each
device (paper §5.3: they "can be sent once at each device at the beginning
of the execution"): :class:`SequenceBank` lays each task's sequences out
and makes them resident with ``ensure_resident``; every task binds them by
name (``present`` maps), so a solve moves only the scores.

Planning: each pair is ordered so that the shorter sequence is the query.
Pairs are grouped by the rung of :data:`LADDER` that first exceeds the
reference's length (the DP row's width, column 0 included), sorted by
query length within a rung, and cut into tasks of equal pair count, padded
to whole grid steps of the kernel (:func:`tiles_per_step`) by repeating a
task's first pair.  A task computes rows
up to its longest query, rounded up to :data:`ROW_BLOCK`.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ClusterRuntime, KernelTable, MapSpec, TaskGraph, TaskNode
from repro.core import run_graph

AA = 20                     # amino acids
#: the residue code of padding: its substitution scores are all 0
PAD = AA
#: ClustalW's integer scale: one matrix unit is INT_SCALE score units
INT_SCALE = 100
#: padded DP row widths; a reference of length n takes the first rung > n
LADDER = (128, 256, 384, 512, 768, 1024, 1536)
#: a task's pairs times its rung width, at most
ROW_CELLS = 1 << 17
#: DP rows per grid step of the kernel (divides every rung)
ROW_BLOCK = 128
#: pairs per tile: one sublane tile
PAIR_TILE = 8
#: one-hot slots per residue code (AA + PAD, padded): 8 pairs' slots make
#: one 256-deep contraction
SLOTS = 32
#: "minus infinity" of the recurrences; far from int32's end, so a few gap
#: costs taken from it cannot wrap
NEG = -(1 << 30)


def _one_hot(x: jax.Array, classes: int) -> jax.Array:
    return (x[..., None] == jnp.arange(classes, dtype=x.dtype)).astype(jnp.int8)


def _operands(queries, refs, subst) -> Tuple[jax.Array, jax.Array]:
    """The two factors of every cell's ``S[a_i][b_j]``, per tile of
    :data:`PAIR_TILE` pairs: one-hot query residues, block-diagonal over
    the tile's pairs ([T, R * 8, 8 * SLOTS]; row ``8i + p`` is pair p's row
    i), and the references' profiles ([T, 8 * SLOTS, W]; row ``SLOTS * p +
    d`` is ``S[d][b_j]`` of pair p).  Their product, in bfloat16 with a
    float32 result, is exact: one term per cell."""
    P, R = queries.shape
    T, A = P // PAIR_TILE, subst.shape[0]
    s = jnp.zeros((SLOTS, SLOTS), jnp.int8).at[:A, :A].set(
        subst.astype(jnp.int8))
    prof = jnp.einsum("pjc,dc->pdj", _one_hot(refs, SLOTS), s,
                      preferred_element_type=jnp.int32)
    prof = prof.astype(jnp.bfloat16).reshape(T, PAIR_TILE * SLOTS, -1)
    hot = _one_hot(queries, SLOTS).reshape(T, PAIR_TILE, R, SLOTS)
    hot = jnp.einsum("tpid,pq->tipqd", hot,
                     jnp.eye(PAIR_TILE, dtype=jnp.int8))
    return (hot.astype(jnp.bfloat16).reshape(T, R * PAIR_TILE, -1), prof)


def _forward_kernel(ge_ref, hot_ref, prof_ref, go_ref, out_ref,
                    h_ref, f_ref, best_ref, sub_ref):
    """``ROW_BLOCK`` rows of one grid step's pair tiles, side by side;
    ``H``, ``F`` and the running maximum persist in scratch from one row
    block to the next.

    Each tile's substitution scores for the block come from one matrix
    product on the chip.  Every array holds a value plus ``j * gh`` at column j: the
    diagonal term then gains ``gh``, ``F`` keeps its form, and ``E[j] +
    j*gh`` is the exclusive prefix maximum of ``H'[k] + k*gh`` less ``g``,
    where ``H'`` is the cell's maximum without ``E`` (a gap continued from
    an ``E`` cell costs less than one reopened from it, as ``g >= 0``)."""
    rb = pl.program_id(1)
    T, W = h_ref.shape
    tiles = sub_ref.shape[0]
    ge = ge_ref[0]
    col = lax.broadcasted_iota(jnp.int32, (T, W), 1)
    colge = col * ge
    go = go_ref[...]                                   # [T, 1]
    gog = go + ge
    lane = lax.broadcasted_iota(jnp.int32, (T, 128), 1)

    @pl.when(rb == 0)
    def _():
        h_ref[...] = colge                             # H[0][j] = 0
        f_ref[...] = jnp.full((T, W), NEG, jnp.int32)
        best_ref[...] = colge

    for b in range(tiles):
        sub_ref[b] = jnp.dot(hot_ref[b], prof_ref[b],
                             preferred_element_type=jnp.float32
                             ).astype(jnp.int32) * INT_SCALE + ge

    def shift(tiles, k):
        """The row moved k < 128 columns right, NEG in the first k: one
        lane rotation per 128-lane tile and a select of its neighbour's."""
        rot = [pltpu.roll(t, k, 1) for t in tiles]
        head = lane < k
        return [jnp.where(head, rot[c - 1] if c else NEG, rot[c])
                for c in range(len(tiles))]

    def prefix_max(tiles):
        """``y[j] = max(x[:j])``, NEG at j = 0."""
        y = shift(tiles, 1)
        k = 1
        while k < 128:
            y = [jnp.maximum(a, b) for a, b in zip(y, shift(y, k))]
            k *= 2
        # past a lane tile, shifts are whole tiles
        d = 1
        while d < len(y):
            y = y[:d] + [jnp.maximum(y[c], y[c - d])
                         for c in range(d, len(y))]
            d *= 2
        return y

    def split(x):
        return [x[:, c:c + 128] for c in range(0, W, 128)]

    def join(parts, axis=1):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)

    def row(i, carry):
        h, f, best = carry
        at = pl.ds(pl.multiple_of(i * PAIR_TILE, PAIR_TILE), PAIR_TILE)
        s = join([sub_ref[b, at, :] for b in range(tiles)], 0)
        f = jnp.maximum(f - ge, h - gog)
        diag = join(shift(split(h), 1))
        hp = jnp.maximum(jnp.maximum(diag + s, f), colge)
        h = jnp.maximum(hp, join(prefix_max(split(hp))) - go)
        return h, f, jnp.maximum(best, h)

    h, f, best = lax.fori_loop(
        0, sub_ref.shape[1] // PAIR_TILE, row,
        (h_ref[...], f_ref[...], best_ref[...]))
    h_ref[...], f_ref[...], best_ref[...] = h, f, best

    @pl.when(rb == pl.num_programs(1) - 1)
    def _():
        top = jnp.max(best - colge, axis=1, keepdims=True)
        out_ref[...] = jnp.broadcast_to(top, out_ref.shape)


def tiles_per_step(width: int) -> int:
    """Pair tiles a grid step runs side by side at a row width: independent
    rows to hide the latency of a row's chain of lane rotations, up to 12
    vector registers per DP array."""
    return next(n for n in (4, 2, 1) if n * width <= 12 * 128)


def forward_scores(hot: jax.Array, prof: jax.Array, gap_open: jax.Array,
                   gap_extend, *, tiles: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """[P] best local scores from the factors of the substitution scores
    (:func:`_operands`), per-pair gap opens [P] and the gap extend;
    ``tiles`` pair tiles per grid step (:func:`tiles_per_step`)."""
    T, R8, K = hot.shape
    W = prof.shape[2]
    P = T * PAIR_TILE
    if tiles is None:
        tiles = tiles_per_step(W)
        while T % tiles:
            tiles //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = ROW_BLOCK * PAIR_TILE
    n = tiles * PAIR_TILE
    out = pl.pallas_call(
        _forward_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // tiles, R8 // rows),
            in_specs=[pl.BlockSpec((tiles, rows, K),
                                   lambda t, r, ge: (t, r, 0)),
                      pl.BlockSpec((tiles, K, W), lambda t, r, ge: (t, 0, 0)),
                      pl.BlockSpec((n, 1), lambda t, r, ge: (t, 0))],
            out_specs=pl.BlockSpec((n, 128), lambda t, r, ge: (t, 0)),
            scratch_shapes=[pltpu.VMEM((n, W), jnp.int32)] * 3
            + [pltpu.VMEM((tiles, rows, W), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((P, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(gap_extend, jnp.int32), (1,)), hot, prof,
      gap_open.astype(jnp.int32)[:, None])
    return out[:, 0]


def align_pairs(queries, refs, subst, gap_open, gap_extend):
    """ClustalW forward-pass scores of a block of pairs, in int32.

    ``queries`` [P, R] holds each pair's query residues at rows 1..R
    (:data:`PAD` past its length), ``refs`` [P, W] its reference's residues
    at columns 1..W-1 (:data:`PAD` at column 0 and past its length),
    ``subst`` [AA + 1, AA + 1] the matrix in its own units with a zero row
    and column for :data:`PAD`, ``gap_open`` [P] each pair's ``g``, and
    ``gap_extend`` ``gh``.  P is a multiple of 8, R of :data:`ROW_BLOCK`, W
    of 128.  Returns ``{"out": [P] int32}``.

    One Pallas kernel keeps 8 to 32 pairs' DP rows on chip and runs the
    recurrence row by row, W columns at a time; each block of rows gets its
    substitution scores from one matrix product of one-hot residues and
    profiles.  A padded cell scores 0 against every residue, so its ``H``
    never exceeds the best real cell's: the maximum over every cell is the
    pair's score.
    """
    hot, prof = _operands(queries, refs, subst)
    return {"out": forward_scores(hot, prof, gap_open, gap_extend)}


def _make_table() -> KernelTable:
    table = KernelTable()
    table.register("align_pairs", align_pairs)
    return table


def mat_avscore(subst) -> int:
    """Minus the mean off-diagonal entry of ``subst`` at :data:`INT_SCALE`,
    truncated toward zero (ClustalW's ``mat_avscore``)."""
    s = np.asarray(subst, np.int64) * INT_SCALE
    off = s[~np.eye(len(s), dtype=bool)]
    return -int(np.trunc(off.sum() / len(off)))


def gap_costs(lengths, subst, pw_go_penalty: float, pw_ge_penalty: float
              ) -> Tuple[np.ndarray, int]:
    """ClustalW's pairwise gap costs: ([N, N] gap open of each pair, gap
    extend), in score units."""
    lengths = np.asarray(lengths, np.int64)
    gg = pw_go_penalty + np.log(np.minimum.outer(lengths, lengths))
    av = mat_avscore(subst)
    g = 2 * av * gg if av > 0 else 2 * INT_SCALE * gg
    return g.astype(np.int64), int(INT_SCALE * pw_ge_penalty)


@dataclass(frozen=True)
class AlignTask:
    """One task of a plan: pairs of one rung, computed in one EXEC."""

    width: int                  # the rung: DP row width, column 0 included
    pairs: np.ndarray           # [P, 2] int32 (query, reference) bank rows
    rows: int                   # DP rows the kernel computes
    work: Dict[str, int]        # cells, padded_cells, bytes (TaskNode.work)


@dataclass(frozen=True)
class AlignPlan:
    tasks: Tuple[AlignTask, ...]
    #: [n(n-1)/2]: where pair k of the (i, j) lexicographic order lies in
    #: the tasks' outputs laid end to end
    order: np.ndarray


def rung(length: int) -> int:
    """The first rung of :data:`LADDER` longer than ``length``."""
    for w in LADDER:
        if w > length:
            return w
    raise ValueError(f"sequence of length {length} is longer than the "
                     f"ladder allows ({LADDER[-1] - 1})")


def plan_pairs(lengths) -> AlignPlan:
    """The tasks that align every pair ``i < j`` of a bank once."""
    lengths = np.asarray(lengths, dtype=np.int64)
    N = len(lengths)
    groups: Dict[int, List[Tuple[int, int, int, int]]] = {}
    k = 0
    for i in range(N):
        for j in range(i + 1, N):
            qi, ri = (i, j) if lengths[i] <= lengths[j] else (j, i)
            groups.setdefault(rung(int(lengths[ri])), []).append(
                (int(lengths[qi]), k, qi, ri))
            k += 1
    tasks: List[AlignTask] = []
    order = np.empty(k, dtype=np.int32)
    offset = 0
    for width in sorted(groups):
        group = sorted(groups[width])
        unit = PAIR_TILE * tiles_per_step(width)
        cap = max(unit, ROW_CELLS // width // unit * unit)
        n_tasks = math.ceil(len(group) / cap)
        P = unit * math.ceil(math.ceil(len(group) / n_tasks) / unit)
        for t in range(n_tasks):
            chunk = group[t * P:(t + 1) * P]
            idx = np.array([(qi, ri) for _, _, qi, ri in chunk], np.int32)
            pairs = np.concatenate([idx, np.repeat(idx[:1], P - len(idx), 0)])
            rows = ROW_BLOCK * math.ceil(chunk[-1][0] / ROW_BLOCK)
            for pos, (_, kk, _, _) in enumerate(chunk):
                order[kk] = offset + pos
            offset += P
            # operands (queries, references, gap opens) and scores
            work = {"cells": int(sum(lengths[qi] * lengths[ri]
                                     for _, _, qi, ri in chunk)),
                    "padded_cells": P * rows * width,
                    "bytes": 4 * (P * (rows + width + 2)
                                  + (AA + 1) * (AA + 1))}
            tasks.append(AlignTask(width, pairs, rows, work))
    return AlignPlan(tuple(tasks), order)


@jax.jit
def _assemble(parts, order):
    return jnp.concatenate(parts)[order]


_bank_ids = itertools.count()


class SequenceBank:
    """A bank of sequences to align all against all: its data, the plan of
    its pairs, and the names under which both are resident on devices.

    ``seqs`` [N, >= max length] holds residues 0..19 (anything past a
    sequence's length is ignored); ``subst`` is a symmetric [AA, AA]
    integer matrix in its own units (a pair's score does not depend on
    which sequence is the query) with entries in int8's range;
    ``pw_go_penalty`` and ``pw_ge_penalty`` are ClustalW's pairwise gap
    penalties (:func:`gap_costs`).  Each bank's resident entries carry a
    name of its own.
    """

    def __init__(self, seqs, lengths, subst, *, pw_go_penalty: float,
                 pw_ge_penalty: float) -> None:
        lengths = np.asarray(lengths, dtype=np.int32)
        seqs = np.asarray(seqs)
        subst = np.asarray(subst)
        if lengths.ndim != 1 or len(lengths) < 2 or lengths.min() < 1:
            raise ValueError("a bank needs two or more sequences, each of "
                             "length at least 1")
        if seqs.shape[0] != len(lengths) or seqs.shape[1] < lengths.max():
            raise ValueError(f"seqs {seqs.shape} does not hold sequences of "
                             f"lengths up to {lengths.max()}")
        if seqs.min() < 0 or seqs.max() >= AA:
            raise ValueError(f"residues must lie in 0..{AA - 1}")
        if (subst.shape != (AA, AA) or not np.array_equal(subst, subst.T)
                or np.abs(subst).max() > 127):
            raise ValueError("subst must be a symmetric 20x20 integer "
                             "matrix with entries in int8's range")
        if pw_go_penalty < 0 or pw_ge_penalty < 0:
            raise ValueError("gap penalties must be non-negative")
        self.name = f"bank{next(_bank_ids)}"
        self.plan = plan_pairs(lengths)
        gap_open, self.gap_extend = gap_costs(lengths, subst, pw_go_penalty,
                                              pw_ge_penalty)
        # residues at positions 1..length, PAD at 0 and past the length
        width = LADDER[-1]
        padded = np.full((len(lengths), width + 1), PAD, np.int32)
        cols = np.arange(1, width + 1)[None, :]
        padded[:, 1:] = np.where(cols <= lengths[:, None],
                                 np.pad(seqs, ((0, 0), (0, width)))[:, :width],
                                 PAD)
        s = np.zeros((AA + 1, AA + 1), np.int32)
        s[:AA, :AA] = subst
        self.values: Dict[str, jax.Array] = {
            f"{self.name}:subst": jnp.asarray(s)}
        for k, t in enumerate(self.plan.tasks):
            q, r = t.pairs[:, 0], t.pairs[:, 1]
            self.values[f"{self.name}:q{k}"] = jnp.asarray(
                padded[q, 1:t.rows + 1])
            self.values[f"{self.name}:r{k}"] = jnp.asarray(
                padded[r, :t.width])
            self.values[f"{self.name}:g{k}"] = jnp.asarray(
                gap_open[q, r].astype(np.int32))
        self.order = jnp.asarray(self.plan.order)

    def install(self, ex) -> None:
        """Make the bank resident on every device of ``ex``'s pool
        (``ensure_resident``: sent where absent, a no-op where the entry
        already holds these arrays)."""
        for d in range(len(ex.pool)):
            ex.ensure_resident(d, self.name, **self.values)

    def release(self, ex) -> None:
        """Drop the bank's entries from every device of ``ex``'s pool."""
        pool = ex.pool
        for d in range(len(pool)):
            held = [n for n in self.values
                    if pool.present[d].get(n) is not None]
            if held:
                ex.exit_data(d, *held)

    def nodes(self, tag: str) -> List[TaskNode]:
        """One node per task of the plan, its maps binding the bank."""
        b = self.name

        def maps(k: int, t: AlignTask) -> MapSpec:
            return MapSpec(
                present={"queries": f"{b}:q{k}", "refs": f"{b}:r{k}",
                         "subst": f"{b}:subst", "gap_open": f"{b}:g{k}"},
                from_={"out": jax.ShapeDtypeStruct((len(t.pairs),),
                                                   jnp.int32)},
                firstprivate={"gap_extend": self.gap_extend})

        return [TaskNode(name=f"{tag}{k}", kernel="align_pairs",
                         make_maps=lambda deps, k=k, t=t: maps(k, t),
                         work=dict(t.work))
                for k, t in enumerate(self.plan.tasks)]


def align_all_pairs(ex, bank: SequenceBank, *, policy=None,
                    nowait: bool = True, tag: str = "align") -> jax.Array:
    """Scores of every pair ``i < j`` of ``bank``, in (i, j) lexicographic
    order, as one int32 device array.

    The bank must be resident on every device of ``ex``'s pool
    (:meth:`SequenceBank.install`); the plan's tasks run as one wave of a
    :class:`TaskGraph` through ``run_graph``.
    """
    pool = ex.pool
    absent = [d for d in range(len(pool))
              if pool.present[d].get(f"{bank.name}:subst") is None]
    if absent:
        raise RuntimeError(f"bank {bank.name} is not resident on devices "
                           f"{absent}: install it first")
    nodes = bank.nodes(tag)
    res = run_graph(ex, TaskGraph(nodes), policy=policy, nowait=nowait,
                    tag=tag)
    parts = [res[nd.name] for nd in nodes]
    home = parts[0].sharding
    return _assemble([jax.device_put(p, home) for p in parts], bank.order)


def _data(n: int, median: int, seed: int = 0) -> SequenceBank:
    """A small bank: lognormal lengths, uniform residues, a symmetric
    substitution matrix with BLOSUM62's ranges, ClustalW's pairwise gap
    penalties."""
    rng = np.random.default_rng(seed)
    lengths = np.clip(np.rint(median * np.exp(0.6 * rng.standard_normal(n))),
                      1, LADDER[-1] - 1).astype(np.int32)
    seqs = rng.integers(0, AA, (n, int(lengths.max())))
    s = rng.integers(-4, 4, (AA, AA))
    subst = np.triu(s) + np.triu(s, 1).T
    np.fill_diagonal(subst, rng.integers(4, 12, AA))
    return SequenceBank(seqs, lengths, subst, pw_go_penalty=10.0,
                        pw_ge_penalty=0.1)


def run(size: str = "small", device_counts=(1, 2, 4, 8)):
    from .common import run_curve
    n, median = {"small": (24, 40), "large": (64, 120)}[size]
    bank = _data(n, median)
    table = _make_table()

    def workload(rt: ClusterRuntime, n: int):
        bank.install(rt.ex)
        return align_all_pairs(rt.ex, bank, nowait=False)

    def serial(rt: ClusterRuntime):
        bank.install(rt.ex)
        return align_all_pairs(rt.ex, bank, nowait=False)

    return run_curve("alignment", size, table, workload, serial=serial,
                     device_counts=device_counts)


if __name__ == "__main__":
    for size in ("small", "large"):
        print(run(size).render())
