"""Training substrate: optimizer math, microbatch equivalence, loss descent,
gradient compression contracts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_smoke_config, smoke_batch
from repro.core import compression as comp
from repro.data import DataConfig, SyntheticLM
from repro.models.model import Model
from repro.optim import AdamW, AdamWConfig
from repro.optim.schedule import cosine_warmup
from repro.train.steps import make_train_step


# ---------------------------------------------------------------------------
# AdamW against a hand-rolled numpy oracle
# ---------------------------------------------------------------------------
def test_adamw_matches_numpy_reference():
    cfg = AdamWConfig(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.1,
                      clip_norm=0.0)
    opt = AdamW(cfg)
    p = {"w": jnp.asarray([[1.0, -2.0], [3.0, 0.5]])}
    g = {"w": jnp.asarray([[0.1, -0.2], [0.3, 0.0]])}
    state = opt.init(p)
    new_p, state, _ = opt.update(g, state, p)

    # numpy reference (step 1)
    gw = np.asarray(g["w"]); pw = np.asarray(p["w"])
    m = (1 - cfg.b1) * gw
    v = (1 - cfg.b2) * gw * gw
    mhat = m / (1 - cfg.b1)
    vhat = v / (1 - cfg.b2)
    want = pw - cfg.lr * (mhat / (np.sqrt(vhat) + cfg.eps) + cfg.weight_decay * pw)
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-6)


def test_adamw_grad_clipping():
    opt = AdamW(AdamWConfig(lr=1e-2, clip_norm=1.0))
    p = {"w": jnp.zeros(4)}
    g = {"w": jnp.full(4, 100.0)}                  # norm 200 >> 1
    state = opt.init(p)
    _, _, metrics = opt.update(g, state, p)
    assert float(metrics["grad_norm"]) > 100.0     # reported pre-clip


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "int8"])
def test_adamw_state_dtypes_step(state_dtype):
    opt = AdamW(AdamWConfig(lr=1e-3, state_dtype=state_dtype))
    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 16))}
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 16)) * 0.1}
    state = opt.init(p)
    for _ in range(3):
        p, state, m = opt.update(g, state, p)
    assert bool(jnp.isfinite(p["w"]).all())


def test_cosine_warmup_schedule():
    lr = cosine_warmup(1.0, warmup_steps=10, total_steps=110, min_ratio=0.1)
    assert float(lr(0)) == 0.0
    np.testing.assert_allclose(float(lr(10)), 1.0, rtol=1e-5)
    assert float(lr(5)) == pytest.approx(0.5, rel=1e-5)
    np.testing.assert_allclose(float(lr(110)), 0.1, rtol=1e-4)
    assert float(lr(60)) < float(lr(20))


# ---------------------------------------------------------------------------
# microbatching == big batch (gradient accumulation correctness)
# ---------------------------------------------------------------------------
def test_microbatch_equivalence():
    cfg = get_smoke_config("minitron-4b").replace(remat="none",
                                                  param_dtype="float32",
                                                  compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = smoke_batch(cfg, batch=4, seq=16)
    opt = AdamW(AdamWConfig(lr=1e-3))
    s1 = jax.jit(make_train_step(model, opt, microbatches=1))
    s2 = jax.jit(make_train_step(model, opt, microbatches=2))
    p1, _, m1 = s1(params, opt.init(params), batch)
    p2, _, m2 = s2(params, opt.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    assert max(jax.tree.leaves(d)) < 5e-5


# ---------------------------------------------------------------------------
# end-to-end descent on the synthetic pipeline
# ---------------------------------------------------------------------------
def test_loss_decreases_on_synthetic_data():
    cfg = get_smoke_config("mamba2-130m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(AdamWConfig(lr=3e-3))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq=32, global_batch=8),
                       process_index=0, process_count=1)
    losses = []
    for i in range(30):
        batch = jax.tree.map(jnp.asarray, data.batch(i))
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses


# ---------------------------------------------------------------------------
# gradient compression (int8 + error feedback)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(1, 400), st.floats(0.01, 100.0))
def test_compress_roundtrip_bounded_error(n, scale):
    x = np.linspace(-scale, scale, n, dtype=np.float32)
    c = comp.compress(jnp.asarray(x))
    y = np.asarray(comp.decompress(c, x.shape))
    # int8 with per-block scale: error ≤ scale_block/2 ≤ max|block|/254*2
    assert np.abs(y - x).max() <= scale / 127.0 + 1e-6


def test_error_feedback_telescopes():
    """Over T rounds, Σ decompressed == Σ inputs − final residual (exactly)."""
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal(37).astype(np.float32))
          for _ in range(8)]
    residual = comp.ef_init(xs[0])
    total_sent = jnp.zeros(37)
    for x in xs:
        c, residual = comp.ef_compress(x, residual)
        total_sent = total_sent + comp.decompress(c, x.shape)
    want = sum(np.asarray(x) for x in xs)
    np.testing.assert_allclose(np.asarray(total_sent) + np.asarray(residual),
                               want, rtol=1e-5, atol=1e-5)


def test_compressed_bytes_are_4x_smaller():
    x = jnp.ones((1024,))
    c = comp.compress(x)
    assert comp.compressed_nbytes(c) < 0.3 * x.size * 4


# ---------------------------------------------------------------------------
# host-mediated vs direct DP fabric (ClusterRuntime)
# ---------------------------------------------------------------------------
def test_data_parallel_grads_modes_agree():
    """Both comm topologies produce the same mean gradient; the funnel costs
    more host traffic (the paper's central finding, at unit-test scale)."""
    from repro.core import ClusterRuntime, RuntimeConfig, KernelTable

    table = KernelTable()

    @table.kernel("gradk")
    def gradk(params, batch):
        # grad of 0.5*||w*x - y||² wrt w
        w = params["w"]
        x, y = batch["x"], batch["y"]
        return {"grads": {"w": (w * x - y) * x}}

    batches = [{"x": jnp.full(4, float(i + 1)), "y": jnp.ones(4)}
               for i in range(3)]
    params = {"w": jnp.full(4, 2.0)}

    def run(mode):
        rt = ClusterRuntime(RuntimeConfig(n_virtual=3, comm_mode=mode),
                            table=table)
        g = rt.data_parallel_grads("gradk", params, batches)
        stats = rt.cost.summary()
        rt.shutdown()
        return g, stats

    g_host, s_host = run("host-mediated")
    g_direct, s_direct = run("direct")
    np.testing.assert_allclose(np.asarray(g_host["w"]),
                               np.asarray(g_direct["w"]), rtol=1e-6)
    want = sum(np.asarray((params["w"] * b["x"] - b["y"]) * b["x"])
               for b in batches) / 3
    np.testing.assert_allclose(np.asarray(g_direct["w"]), want, rtol=1e-6)
    # the host funnel moves ≥ direct mode's bytes through the host NIC
    assert s_host["bytes_from"] >= s_direct["bytes_from"]
