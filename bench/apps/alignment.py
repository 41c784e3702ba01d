"""BOTS alignment on the offload runtime: the bank, one solve, its check.

The kernel and the planner are the program's (``benchmarks/bots_alignment``:
``align_pairs``, ``SequenceBank``, ``align_all_pairs``).  The benchmark owns
the rest: the bank drawn from the seed as the configuration's ``assumed``
says, the count of the work a solve does, and the comparison with the plain
reference in ``alignment_ref.py``.

The bank is made resident on every device when the solver is built, in
set-up.  One solve aligns every pair of the bank through ``run_graph`` and
ends when the scores, one int32 array in (i, j) lexicographic order, are
ready.  Every solve is the same: BOTS aligns one set.
"""
from __future__ import annotations

import os
import sys
from statistics import NormalDist
from typing import Any, Dict, Tuple

import jax
import numpy as np

from bots_alignment import SequenceBank, _make_table, align_all_pairs

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import alignment_ref  # noqa: E402  (beside this file)


def make_table(cfg: Dict[str, Any]):
    return _make_table()


def bank_inputs(cfg: Dict[str, Any], seed: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(residues [N, length_max], lengths [N], substitution [A, A]), all
    int32.  The lengths are the N quantiles ``(k + 1/2) / N`` of a
    lognormal about ``length_median`` with ``length_sigma``, rounded and
    clipped to [``length_min``, ``length_max``], in an order drawn from the
    seed: one length profile, as one input file has, so every seed's bank
    asks the same work.  Residues uniform over the alphabet; a symmetric
    substitution matrix, diagonal and off-diagonal entries uniform in their
    configured ranges."""
    rng = np.random.default_rng(seed)
    N, A = cfg["sequences"], cfg["alphabet"]
    z = np.array([NormalDist().inv_cdf((k + 0.5) / N) for k in range(N)])
    lengths = np.clip(np.rint(cfg["length_median"]
                              * np.exp(cfg["length_sigma"] * z)),
                      cfg["length_min"], cfg["length_max"]).astype(np.int32)
    lengths = rng.permutation(lengths)
    seqs = rng.integers(0, A, (N, cfg["length_max"]), dtype=np.int32)
    lo, hi = cfg["substitution"]["off_diagonal"]
    off = rng.integers(lo, hi + 1, (A, A), dtype=np.int32)
    subst = np.triu(off, 1) + np.triu(off, 1).T
    lo, hi = cfg["substitution"]["diagonal"]
    np.fill_diagonal(subst, rng.integers(lo, hi + 1, A, dtype=np.int32))
    return seqs, lengths, subst


def cells_per_solve(cfg: Dict[str, Any], seed: int) -> int:
    """DP cells of one solve's forward passes at the true lengths: the sum
    of ``m * n`` over every pair of the bank."""
    lengths = bank_inputs(cfg, seed)[1].astype(np.int64)
    return int((lengths.sum() ** 2 - (lengths ** 2).sum()) // 2)


class Solver:
    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                 rt) -> None:
        self.traffic, self.rt = traffic, rt
        seqs, lengths, subst = bank_inputs(cfg, seed)
        self.bank = SequenceBank(seqs, lengths, subst,
                                 pw_go_penalty=cfg["pw_go_penalty"],
                                 pw_ge_penalty=cfg["pw_ge_penalty"])
        self.bank.install(rt.ex)
        self.tasks_per_solve = len(self.bank.plan.tasks)

    def solve(self, i: int) -> jax.Array:
        return jax.block_until_ready(align_all_pairs(
            self.rt.ex, self.bank, policy=self.traffic["policy"],
            nowait=self.traffic["nowait"]))

    def input_of(self, i: int) -> int:
        return 0

    def close(self) -> None:
        self.bank.release(self.rt.ex)
        self.bank = None


def host_copy(out: jax.Array) -> np.ndarray:
    return np.asarray(out)


def references(cfg: Dict[str, Any], seed: int, n: int, inputs,
               control: bool = False) -> Dict[int, np.ndarray]:
    """The scores of the one bank; bfloat16 with ``control``."""
    import jax.numpy as jnp
    seqs, lengths, subst = bank_inputs(cfg, seed)
    ref = alignment_ref.scores(seqs, lengths, subst, cfg["int_scale"],
                               cfg["pw_go_penalty"], cfg["pw_ge_penalty"],
                               dtype=jnp.bfloat16 if control else jnp.int32)
    return {t: ref for t in set(inputs)}


def compare(cfg: Dict[str, Any], produced: np.ndarray,
            ref: np.ndarray) -> Dict[str, float]:
    """Share of the pairs whose score differs from the reference's."""
    return {"scores_differing": float(np.mean(produced != ref))}


def control_output(cfg: Dict[str, Any], produced: np.ndarray,
                   ref_low: np.ndarray) -> np.ndarray:
    return ref_low
