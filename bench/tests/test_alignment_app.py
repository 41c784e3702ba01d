"""The alignment cell and the four-chip mandelbrot cell: both load; the
alignment app at a reduced size is correct on the CPU and its bfloat16
control is not; every seed draws one length profile; the work the program
declares is the benchmark's own count; the three alignment readers."""
import copy
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import readings, run, spec
from bench.tests.conftest import ROOT

ALIGN = "alignment-bots-n100.1chip"
#: a bank whose scores pass 256 (bfloat16's last exact integer), on 2 rungs
SMALL = {"sequences": 12, "length_median": 300, "length_max": 500}


def small_alignment():
    cell = copy.deepcopy(spec.load_cell(ROOT, ALIGN))
    cell.config.update(SMALL)
    return cell


@pytest.mark.parametrize("name,chips,pool", [
    (ALIGN, 1, {"layout": "virtual", "devices": 4}),
    ("mandelbrot-4600.4chip", 4, {"layout": "chips", "devices": 4})])
def test_new_cells_load(name, chips, pool):
    cell = spec.load_cell(ROOT, name)
    assert cell.chips == chips and cell.traffic["pool"] == pool
    assert cell.traffic["inputs"] == 1 and cell.traffic["nowait"]
    assert not cell.traffic["peer"]
    assert {m["name"] for m in cell.end_to_end} >= {"solve_s", "setup_s"}
    align = {"align_gcups", "align_pad_share", "align_hbm_share"}
    names = {m["name"] for m in cell.per_layer}
    assert (align <= names) is (name == ALIGN)
    assert spec.app_module(cell).make_table(cell.config) is not None


def test_small_run_is_correct_and_the_control_is_not():
    cell = small_alignment()
    result, info = run.run_cell(cell, seed=2 ** 33 + 7, seconds=0.3,
                                trace=False, t_start=time.perf_counter(),
                                compile_cache=False)
    assert result["correct"], info
    assert result["checks"]["scores_differing"]["value"] == 0.0
    for seed in (1, 2 ** 31 + 3):
        got = readings.control_readings(cell, seed, [(1, None)], [0])
        assert got["scores_differing"] > 0.0, seed


def test_every_seed_draws_one_length_profile():
    """The seed orders the lengths and draws the residues and ``S``; the
    lengths themselves, and so the plan and the work, are the same."""
    cell = spec.load_cell(ROOT, ALIGN)
    app = spec.app_module(cell)
    banks = [app.bank_inputs(cell.config, s) for s in (1, 2 ** 31 + 9)]
    (seqs0, len0, s0), (seqs1, len1, s1) = banks
    assert len(len0) == cell.config["sequences"] == 100
    assert not np.array_equal(len0, len1)
    np.testing.assert_array_equal(np.sort(len0), np.sort(len1))
    assert not np.array_equal(seqs0, seqs1) and not np.array_equal(s0, s1)
    lo, hi = cell.config["length_min"], cell.config["length_max"]
    assert lo <= len0.min() and len0.max() <= hi
    assert abs(np.median(len0) - cell.config["length_median"]) <= 2
    assert app.cells_per_solve(cell.config, 1) == \
        app.cells_per_solve(cell.config, 2 ** 31 + 9)


def test_declared_work_is_the_benchmarks_count():
    cell = small_alignment()
    app, rt = run.prepare(cell, compile_cache=False)
    try:
        seed = 3_000_000_021
        solver = app.Solver(cell.config, cell.traffic, seed, rt)
        k0 = len(rt.pool.cost.compute)
        solver.solve(1)
        records = rt.pool.cost.compute[k0:]
        assert len(records) == solver.tasks_per_solve
        assert sum(r.work["cells"] for r in records) == \
            app.cells_per_solve(cell.config, seed)
    finally:
        rt.shutdown()


FOUR_CHIPS = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
from bench.tests.conftest import small_cell
from bench import run, spec
cell = small_cell("mandelbrot-4600.1chip")
four = spec.load_cell(sys.argv[1], "mandelbrot-4600.4chip")
cell.traffic, cell.chips = four.traffic, four.chips
result, info = run.run_cell(cell, seed=2**31 + 11, seconds=0.3, trace=False,
                            t_start=time.perf_counter(), compile_cache=False)
print("\n".join(info))
print(json.dumps(result))
"""


def test_mandelbrot_strips_on_four_virtual_chips():
    """The four-chip cell's pool at a small image: one strip per device,
    one EXEC on each per solve, the image assembled and correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_CHIPS, ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["device"]["count"] == 4
    solves = result["attempted"]
    execs = next(l for l in p.stdout.splitlines() if "EXECs per device" in l)
    assert f"EXECs per device {[solves] * 4}" in execs


def _ctx(records, device_ops, busy=2.25):
    trace = SimpleNamespace(device_ops=device_ops, busy_s=[busy])
    return SimpleNamespace(compute=records, trace=trace)


def test_alignment_readers():
    cell = small_alignment()
    rec = lambda work: SimpleNamespace(work=work)
    records = [rec({"cells": 3e9, "padded_cells": 4e9, "bytes": 8.19e11}),
               rec({"cells": 1e9, "padded_cells": 1e9, "bytes": 0}),
               rec(None)]
    ops = [("jit_align_pairs_123_", 1.5), ("jit__assemble_9_", 0.25),
           ("jit_align_pairs_456_", 0.5)]
    read = {m: spec.metric_reader(cell, m).read
            for m in ("align_gcups", "align_pad_share", "align_hbm_share")}
    assert read["align_gcups"](_ctx(records, ops)) == pytest.approx(2.0)
    assert read["align_pad_share"](_ctx(records, ops)) == pytest.approx(0.2)
    assert read["align_hbm_share"](_ctx(records, ops)) == pytest.approx(0.5)
    # a program that declares no work, or a trace without the kernel
    bare = [SimpleNamespace(seconds=1.0)]
    for name, fn in read.items():
        assert fn(_ctx(bare, ops)) is None, name
        assert fn(SimpleNamespace(compute=bare, trace=None)) is None, name
    assert read["align_gcups"](_ctx(records, ops[1:2])) is None
    assert read["align_hbm_share"](_ctx(records, [])) is None
    # kernel modules past the reduction's list count as the kernel's
    assert read["align_gcups"](_ctx(records, ops[:2], busy=2.25)) == \
        pytest.approx(2.0)
    assert read["align_gcups"](_ctx(records, ops[:1], busy=4.0)) == \
        pytest.approx(1.0)
