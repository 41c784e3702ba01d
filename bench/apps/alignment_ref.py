"""Plain reference for all-pairs ClustalW forward-pass scores (BOTS alignment).

Independent of the runtime and its kernels.  Pair ``(i, j)``, ``i < j``, in
lexicographic order, aligns sequence ``i`` (length m) with sequence ``j``
(length n) locally with affine gaps, as ClustalW's ``forward_pass`` does::

    H[i][0] = H[0][j] = 0,  E and F start at minus infinity
    E[i][j] = max(E[i][j-1], H[i][j-1] - g) - gh
    F[i][j] = max(F[i-1][j], H[i-1][j] - g) - gh
    H[i][j] = max(H[i-1][j-1] + S[a_i][b_j], E[i][j], F[i][j], 0)

and the score is the largest ``H[i][j]`` with ``i <= m`` and ``j <= n``.
``S`` is the matrix times ``int_scale``; ``gh = int_scale * pw_ge``; ``g =
2 * avscore * (pw_go + ln(min(m, n)))`` truncated, where ``avscore`` is
minus the mean off-diagonal entry of ``S`` truncated toward zero
(``2 * int_scale * (...)`` where that is not positive).

Every sequence is padded to the bank's longest and the recurrence runs row
by row (a ``lax.scan`` over the rows).  Within a row, unrolling ``E`` gives
``E[j] = max_{k<j}(H'[k] - g - (j-k)*gh)`` with ``H'`` the cell's maximum
without ``E``, a running maximum (``lax.cummax``).  The maximum is taken
over the cells inside each pair's true lengths only.  Computed with
``jax.numpy`` on whatever device JAX holds, in blocks of pairs;
``dtype=jnp.bfloat16`` is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG = -(2 ** 30)
HIGHEST = lax.Precision.HIGHEST


def gap_costs(lengths, subst, int_scale: int, pw_go: float, pw_ge: float):
    """([N, N] gap open of each pair, gap extend) in score units."""
    s = np.asarray(subst, np.int64) * int_scale
    off = s[~np.eye(len(s), dtype=bool)]
    avscore = -int(np.trunc(off.sum() / len(off)))
    lengths = np.asarray(lengths, np.int64)
    gg = pw_go + np.log(np.minimum.outer(lengths, lengths))
    g = 2 * avscore * gg if avscore > 0 else 2 * int_scale * gg
    return g.astype(np.int64), int(int_scale * pw_ge)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _block(a, b, m, n, subst, go, ge, *, dtype):
    """Scores of a block of pairs: ``a``, ``b`` [B, L] residues, ``m``,
    ``n``, ``go`` [B]."""
    B, L = a.shape
    alphabet = subst.shape[0]
    # prof[p, c, j] = S[c][b_j], column j = 1..L (column 0 unused)
    prof = jnp.einsum("pjd,cd->pcj", jax.nn.one_hot(b, alphabet),
                      subst.astype(jnp.float32), precision=HIGHEST)
    prof = jnp.pad(prof, ((0, 0), (0, 0), (1, 0)))
    go, ge = go.astype(dtype)[:, None], ge.astype(dtype)
    neg = jnp.asarray(NEG, dtype)
    zero = jnp.zeros((), dtype)
    col = jnp.arange(L + 1)
    in_cols = (col >= 1) & (col[None, :] <= n[:, None])
    colge = col.astype(dtype) * ge
    h0 = jnp.zeros((B, L + 1), dtype)
    f0 = jnp.full_like(h0, neg)

    def row(carry, x):
        h, f, best = carry
        ai, i = x
        s = jnp.einsum("pc,pcj->pj", jax.nn.one_hot(ai, alphabet), prof,
                       precision=HIGHEST).astype(dtype)
        f = jnp.maximum(f, h - go) - ge
        diag = jnp.concatenate([jnp.full((B, 1), neg), h[:, :-1]], axis=1)
        hp = jnp.maximum(jnp.maximum(diag + s, f), zero)
        hp = hp.at[:, 0].set(zero)
        run = lax.cummax(hp + colge, axis=1)
        e = jnp.concatenate([jnp.full((B, 1), neg), run[:, :-1]],
                            axis=1) - go - colge
        h = jnp.maximum(hp, e)
        inside = in_cols & (i <= m)[:, None]
        return (h, f, jnp.maximum(best, jnp.where(inside, h, zero))), None

    (_, _, best), _ = lax.scan(row, (h0, f0, h0),
                               (a.T, jnp.arange(1, L + 1, dtype=jnp.int32)))
    return jnp.max(best, axis=1)


def scores(seqs, lengths, subst, int_scale: int, pw_go: float, pw_ge: float,
           *, dtype=jnp.int32, block: int = 512) -> np.ndarray:
    """[N(N-1)/2] scores of every pair ``i < j``, in lexicographic order;
    ``subst`` in the matrix's own units."""
    lengths = np.asarray(lengths, np.int32)
    L = int(lengths.max())
    seqs = np.asarray(seqs, np.int32)[:, :L]
    i, j = np.triu_indices(len(lengths), k=1)
    g, gh = gap_costs(lengths, subst, int_scale, pw_go, pw_ge)
    go_all = g[i, j].astype(np.int32)
    subst = jnp.asarray(np.asarray(subst, np.int32) * int_scale)
    ge = jnp.int32(gh)
    out = []
    for s in range(0, len(i), block):
        bi, bj, bg = i[s:s + block], j[s:s + block], go_all[s:s + block]
        pad = block - len(bi)                  # one compiled block shape
        bi, bj, bg = np.pad(bi, (0, pad)), np.pad(bj, (0, pad)), np.pad(bg, (0, pad))
        got = _block(jnp.asarray(seqs[bi]), jnp.asarray(seqs[bj]),
                     jnp.asarray(lengths[bi]), jnp.asarray(lengths[bj]),
                     subst, jnp.asarray(bg), ge, dtype=dtype)
        out.append(np.asarray(got).astype(np.int64)[:block - pad])
    return np.concatenate(out)
