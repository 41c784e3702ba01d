"""chip_smoke.py's phases rehearsed on the CPU at tiny sizes.

The script itself refuses to run anywhere but a TPU; these tests call its
phase functions with small shapes (and interpret-mode kernels) so that wrong
paths, arguments and checks show up before a chip run.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from repro.configs.registry import get_smoke_config  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config(chip_smoke.ARCH)


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "phase 0: JAX found no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_kernel_phase(cfg):
    chip_smoke.phase_kernels(cfg, seq=128, batch=2, block=128,
                             strip=(16, 128), max_iter=30, interpret=True)


def test_dag_and_serving_phases_leave_recovery_idle(cfg):
    dag_rt, dag_execs, packed = chip_smoke.phase_dag(K=3, B=32)
    dag_rt.shutdown()
    assert packed.shape == (3, 3, 32, 32)
    serve_rt, serve_execs = chip_smoke.phase_serving(
        cfg, n_req=2, prompt_len=8, new_tokens=4, max_len=32)
    serve_rt.shutdown()
    chip_smoke.phase_recovery([("dag", dag_rt, dag_execs),
                               ("serving", serve_rt, serve_execs)])


def test_recovery_phase_sees_a_strike():
    dag_rt, dag_execs, _ = chip_smoke.phase_dag(K=2, B=16, devices=2)
    dag_rt.shutdown()
    dag_rt.pool.health.mark_failed(1)
    with pytest.raises(chip_smoke.SmokeFailure, match="health strikes"):
        chip_smoke.phase_recovery([("dag", dag_rt, dag_execs)])


def test_four_chip_phase_on_four_cpu_devices():
    """The --chips 4 path on four virtual CPU devices, one per pool device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke; chip_smoke.phase_four_chips(K=4, B=32); "
            "print('done')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.rstrip().endswith("done")
    assert "bit-identical True" in proc.stdout
