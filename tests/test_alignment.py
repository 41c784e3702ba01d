"""BOTS alignment: the ``align_pairs`` kernel and the plain reference against
ClustalW's forward pass written out as BOTS runs it, the gap costs, the
planner, ``align_all_pairs`` on the runtime, the bank
sent once per device, and the work an EXEC declares."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", "benchmarks"))

import bots_alignment as ba  # noqa: E402
from repro.core import (ClusterRuntime, MapSpec, RuntimeConfig, TaskGraph,  # noqa: E402
                        TaskNode, run_graph)


def _load_ref():
    path = os.path.join(HERE, "..", "bench", "apps", "alignment_ref.py")
    spec = importlib.util.spec_from_file_location("alignment_ref_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


alignment_ref = _load_ref()
PW_GO, PW_GE = 10.0, 0.1


def forward_pass(a, b, S, g, gh):
    """ClustalW's ``forward_pass`` as BOTS runs it, over plain lists: the
    best local score with affine gaps (``S`` already scaled)."""
    HH, DD, best = [0] * (len(b) + 1), [-g] * (len(b) + 1), 0
    for i in range(1, len(a) + 1):
        hh = p = 0
        f = -g
        for j in range(1, len(b) + 1):
            f = max(f - gh, hh - g - gh)
            DD[j] = max(DD[j] - gh, HH[j] - g - gh)
            hh = max(p + S[a[i - 1]][b[j - 1]], f, DD[j], 0)
            p, HH[j] = HH[j], hh
            best = max(best, hh)
    return best


def _subst(rng):
    s = rng.integers(-4, 4, (ba.AA, ba.AA))
    S = np.triu(s, 1) + np.triu(s, 1).T
    np.fill_diagonal(S, rng.integers(4, 12, ba.AA))
    return S


def _bank(case):
    """(residues [N, max], lengths, S) of a small seeded bank."""
    rng = np.random.default_rng(CASES.index(case))
    lengths = {"length-1": [1, 1, 7, 1, 40],
               "self": [12, 12, 30, 5],
               "random": list(rng.integers(1, 41, 9))}[case]
    seqs = rng.integers(0, ba.AA, (len(lengths), 40))
    if case == "self":
        seqs[1] = seqs[0]                       # a sequence against itself
    return seqs, np.array(lengths), _subst(rng)


def _textbook(seqs, lengths, S, pairs):
    g, gh = ba.gap_costs(lengths, S, PW_GO, PW_GE)
    return np.array([forward_pass(list(seqs[i][:lengths[i]]),
                                  list(seqs[j][:lengths[j]]),
                                  S * ba.INT_SCALE, int(g[i, j]), gh)
                     for i, j in pairs])


CASES = ["length-1", "self", "random"]


@pytest.mark.parametrize("case", CASES)
def test_reference_matches_textbook(case):
    seqs, lengths, S = _bank(case)
    pairs = list(zip(*np.triu_indices(len(lengths), k=1)))
    got = alignment_ref.scores(seqs, lengths, S, ba.INT_SCALE, PW_GO, PW_GE,
                               block=8)
    np.testing.assert_array_equal(got, _textbook(seqs, lengths, S, pairs))


def _layout(seqs, lengths, pairs, rows, width):
    """Each pair's query residues at rows 1..rows and reference residues at
    columns 1..width-1, PAD elsewhere, as the kernel takes them."""
    n = len(lengths)
    padded = np.full((n, rows + width + 1), ba.PAD, np.int32)
    for k in range(n):
        padded[k, 1:lengths[k] + 1] = seqs[k][:lengths[k]]
    q = np.array([padded[i, 1:rows + 1] for i, _ in pairs])
    r = np.array([padded[j, :width] for _, j in pairs])
    return q, r


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_textbook_both_ways_round(case):
    """Every ordered pair, each query against each reference and swapped:
    the kernel agrees with the textbook, and a swap leaves the score."""
    seqs, lengths, S = _bank(case)
    n = len(lengths)
    pairs = [(i, j) for i in range(n) for j in range(n)]
    pairs += [pairs[0]] * (-len(pairs) % 8)
    g, gh = ba.gap_costs(lengths, S, PW_GO, PW_GE)
    q, r = _layout(seqs, lengths, pairs, ba.ROW_BLOCK, ba.LADDER[0])
    sx = np.zeros((ba.AA + 1, ba.AA + 1), np.int32)
    sx[:ba.AA, :ba.AA] = S
    out = jax.jit(ba.align_pairs)(
        jnp.asarray(q), jnp.asarray(r), jnp.asarray(sx),
        jnp.asarray([g[i, j] for i, j in pairs], jnp.int32), gh)
    got = np.asarray(out["out"])[:n * n].reshape(n, n)
    want = _textbook(seqs, lengths, S, pairs[:n * n]).reshape(n, n)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, got.T)
    if case == "self":
        assert got[0, 1] == ba.INT_SCALE * sum(S[c][c]
                                               for c in seqs[0][:lengths[0]])


def test_gap_costs_are_clustalws():
    """``gh = 100 * pw_ge``; ``g = 2 * mat_avscore * (pw_go + ln(min(m,
    n)))`` truncated, mat_avscore minus the mean off-diagonal entry at
    scale 100; ``2 * 100 * (...)`` where that is not positive; the plain
    reference computes the same costs on its own."""
    S = np.full((ba.AA, ba.AA), -1)
    np.fill_diagonal(S, 5)
    g, gh = ba.gap_costs([10, 300], S, PW_GO, PW_GE)
    assert gh == 10 and ba.mat_avscore(S) == 100
    assert g[0, 1] == g[1, 0] == int(2 * 100 * (10.0 + np.log(10)))
    assert g[1, 1] == int(2 * 100 * (10.0 + np.log(300)))
    np.fill_diagonal(S, 0)
    S[:] = 2
    assert ba.mat_avscore(S) == -200
    g, _ = ba.gap_costs([10, 300], S, PW_GO, PW_GE)
    assert g[0, 1] == int(2 * ba.INT_SCALE * (10.0 + np.log(10)))
    rng = np.random.default_rng(7)
    for _ in range(5):
        S, lengths = _subst(rng), rng.integers(1, 1500, 30)
        g, gh = ba.gap_costs(lengths, S, PW_GO, PW_GE)
        g2, gh2 = alignment_ref.gap_costs(lengths, S, ba.INT_SCALE, PW_GO,
                                          PW_GE)
        np.testing.assert_array_equal(g, g2)
        assert gh == gh2


def _lognormal_lengths(seed, n=100):
    rng = np.random.default_rng(seed)
    return np.clip(np.rint(300 * np.exp(0.6 * rng.standard_normal(n))),
                   50, 1500).astype(np.int32)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_plan_covers_every_pair_once(seed):
    lengths = _lognormal_lengths(seed)
    plan = ba.plan_pairs(lengths)
    N = len(lengths)
    flat = np.concatenate([t.pairs for t in plan.tasks])
    i, j = np.triu_indices(N, k=1)
    placed = flat[plan.order]
    # pair k of the lexicographic order sits at order[k], shorter first
    assert len(set(plan.order.tolist())) == len(i)
    np.testing.assert_array_equal(np.sort(placed, axis=1),
                                  np.stack([i, j], axis=1))
    assert (lengths[placed[:, 0]] <= lengths[placed[:, 1]]).all()
    shapes = set()
    for t in plan.tasks:
        assert t.width in ba.LADDER
        assert len(t.pairs) % (8 * ba.tiles_per_step(t.width)) == 0
        assert (lengths[t.pairs] < t.width).all()
        assert t.rows % ba.ROW_BLOCK == 0
        assert t.rows - ba.ROW_BLOCK < lengths[t.pairs[:, 0]].max() <= t.rows
        assert len(t.pairs) * t.width <= ba.ROW_CELLS
        shapes.add((t.width, len(t.pairs), t.rows))
    assert len(shapes) <= sum(w // ba.ROW_BLOCK for w in ba.LADDER)
    cells = sum(t.work["cells"] for t in plan.tasks)
    assert cells == sum(int(lengths[a]) * int(lengths[b])
                        for a, b in zip(i, j))
    assert 20 <= len(plan.tasks) <= 40


def _small_bank(seed=3):
    rng = np.random.default_rng(seed)
    lengths = np.array([1, 5, 40, 127, 128, 130, 200, 255, 256, 300, 17, 90])
    seqs = rng.integers(0, ba.AA, (len(lengths), 300))
    return seqs, lengths, _subst(rng)


@pytest.fixture
def rt4():
    rt = ClusterRuntime(RuntimeConfig(n_virtual=4), table=ba._make_table())
    yield rt
    rt.shutdown()


def test_align_all_pairs_equals_reference_on_four_devices(rt4):
    seqs, lengths, S = _small_bank()
    bank = ba.SequenceBank(seqs, lengths, S, pw_go_penalty=PW_GO,
                           pw_ge_penalty=PW_GE)
    assert len({t.width for t in bank.plan.tasks}) == 3
    bank.install(rt4.ex)
    got = ba.align_all_pairs(rt4.ex, bank)
    assert got.dtype == jnp.int32
    want = alignment_ref.scores(seqs, lengths, S, ba.INT_SCALE, PW_GO, PW_GE,
                                block=32)
    np.testing.assert_array_equal(np.asarray(got), want)
    records = [r for r in rt4.pool.cost.compute if r.kernel == "align_pairs"]
    assert len(records) == len(bank.plan.tasks)
    assert {r.device for r in records} == set(range(min(4, len(records))))
    i, j = np.triu_indices(len(lengths), k=1)
    assert sum(r.work["cells"] for r in records) == int(
        (lengths[i].astype(np.int64) * lengths[j]).sum())
    for r in records:
        assert r.work["cells"] <= r.work["padded_cells"]


def test_bank_is_sent_once_per_device(rt4):
    seqs, lengths, S = _small_bank()
    bank = ba.SequenceBank(seqs, lengths, S, pw_go_penalty=PW_GO,
                           pw_ge_penalty=PW_GE)
    pool = rt4.pool
    bank.install(rt4.ex)
    first = ba.align_all_pairs(rt4.ex, bank)
    sent = [c for c in pool.trace if c.tag.startswith(f"{bank.name}:")]
    entries = len(bank.values)
    assert sum(c.op == "ALLOC" for c in sent) == 4 * entries
    assert sum(c.op == "XFER_TO" for c in sent) == 4 * entries
    c0 = len(pool.trace)
    second = ba.align_all_pairs(rt4.ex, bank)
    again = pool.trace[c0:]
    assert not [c for c in again if c.tag.startswith(f"{bank.name}:")]
    assert not [c for c in again if c.op == "XFER_TO"]
    assert len(again) == 4 * len(bank.plan.tasks)   # ALLOC, EXEC, XFER_FROM, FREE
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


def test_each_bank_aligns_its_own_sequences(rt4):
    """Banks made one after another on one runtime never share resident
    entries, and a released bank leaves nothing on the devices."""
    seqs, lengths, S = _small_bank()
    first = ba.SequenceBank(seqs, lengths, S, pw_go_penalty=PW_GO,
                            pw_ge_penalty=PW_GE)
    first.install(rt4.ex)
    a = np.asarray(ba.align_all_pairs(rt4.ex, first))
    first.release(rt4.ex)
    assert not any(rt4.pool.present[d].get(n) is not None
                   for d in range(4) for n in first.values)
    seqs2, lengths2, S2 = _small_bank(seed=4)
    second = ba.SequenceBank(seqs2, lengths2[::-1].copy(), S2,
                             pw_go_penalty=PW_GO, pw_ge_penalty=PW_GE)
    with pytest.raises(RuntimeError, match="not resident"):
        ba.align_all_pairs(rt4.ex, second)
    second.install(rt4.ex)
    b = np.asarray(ba.align_all_pairs(rt4.ex, second))
    np.testing.assert_array_equal(a, alignment_ref.scores(
        seqs, lengths, S, ba.INT_SCALE, PW_GO, PW_GE, block=32))
    np.testing.assert_array_equal(b, alignment_ref.scores(
        seqs2, lengths2[::-1], S2, ba.INT_SCALE, PW_GO, PW_GE, block=32))


@pytest.mark.parametrize("bad", ["asymmetric", "too-long", "empty",
                                 "residue", "gap"])
def test_bank_rejects_malformed_input(bad):
    seqs, lengths, S = _small_bank()
    kw = {"pw_go_penalty": PW_GO, "pw_ge_penalty": PW_GE}
    if bad == "asymmetric":
        S = S.copy()
        S[0, 1] += 1
    elif bad == "too-long":
        lengths = lengths.copy()
        lengths[0] = ba.LADDER[-1]
        seqs = np.zeros((len(lengths), ba.LADDER[-1]), np.int32)
    elif bad == "empty":
        lengths = lengths.copy()
        lengths[0] = 0
    elif bad == "residue":
        seqs = seqs.copy()
        seqs[0, 0] = ba.AA
    else:
        kw["pw_go_penalty"] = -1.0
    with pytest.raises(ValueError):
        ba.SequenceBank(seqs, lengths, S, **kw)


def _strip_table():
    from repro.core import KernelTable
    table = KernelTable()
    table.register("double", lambda x: {"out": 2 * x})
    return table


@pytest.mark.parametrize("work", [None, {"cells": 6, "padded_cells": 8,
                                         "bytes": 48}])
def test_exec_record_carries_declared_work(work):
    """A node's declared work lands on its EXEC's record; a node that
    declares none, like every kernel but the alignment's, records None."""
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_strip_table())
    try:
        x = jnp.arange(6.0)
        node = TaskNode(name="t", kernel="double", work=work,
                        make_maps=lambda deps: MapSpec(
                            to={"x": x},
                            from_={"out": jax.ShapeDtypeStruct((6,),
                                                               jnp.float32)}))
        res = run_graph(rt.ex, TaskGraph([node]))
        np.testing.assert_array_equal(np.asarray(res["t"]), 2 * np.arange(6.0))
        rt.target("double", 1, MapSpec(
            to={"x": x}, from_={"out": jax.ShapeDtypeStruct((6,),
                                                            jnp.float32)}))
        first, second = rt.pool.cost.compute
        assert first.work == work
        assert second.work is None
    finally:
        rt.shutdown()


def test_bots_run_small_curve():
    sys.path.insert(0, os.path.join(HERE, ".."))
    from benchmarks import bots_alignment
    curve = bots_alignment.run("small", device_counts=(2,))
    assert curve.serial_s > 0 and curve.points[0].devices == 2
