"""Drive the offload runtime's main path once on a TPU and check the results.

    python chip_smoke.py             # one chip: phases 0-4
    python chip_smoke.py --chips 4   # four chips: phase 0, then the
                                     # one-device-per-chip sparselu phase only

Phases run in one process, in order, and each prints its result lines before
the next starts.  A failed check raises, so the script exits non-zero and
never prints the closing JSON line.

0. Device: JAX must report a TPU first; nothing else runs otherwise.
1. Kernels: flash_attention, flash_decode, block_lu.bmod and mandelbrot run
   compiled (``interpret=False``) at gemma3-4b / BOTS shapes against their
   ``ref.py`` oracles.
2. Task DAG: BOTS sparselu (K=8 blocks of 512x512 f32, 204 tasks) through
   ``ClusterRuntime.wavefront_offload`` on 4 virtual devices, peer-routed,
   locality-placed; relative residual ||L.U - A|| / ||A|| of the result.
3. Serving: gemma3-4b at full width with seeded random weights, 4 greedy
   requests through ``ServeEngine`` on the runtime's pool, checked against
   the local continuous path on the same weights.
4. Recovery did nothing: no absorbed device failure, no health strike, no
   funnel fallback, no hedge, and exactly one EXEC per task.

The last line of standard output is the contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Inputs and weights are generated from ``--seed``; nothing is read from disk.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from typing import Any, Dict, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(HERE, "benchmarks"), os.path.join(HERE, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax
import jax.numpy as jnp
import numpy as np

from bots_sparselu import _build_dag, _make_table, _matrix
from repro.configs.registry import get_config
from repro.core import ClusterRuntime, DevicePool, MapSpec, RuntimeConfig
from repro.kernels.block_lu.block_lu import bmod
from repro.kernels.block_lu.ref import bmod_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.flash_decode import flash_decode
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.kernels.mandelbrot.ops import mandelbrot_strip
from repro.kernels.mandelbrot.ref import mandelbrot_ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serve import Request, ServeConfig, ServeEngine

ARCH = "gemma3-4b"
#: phase 1's shapes: gemma3-4b attention over a 2048-token prompt, and over a
#: 2048-slot cache at decode batch 4; one 512x512 sparselu block; a 256-row
#: strip of a 1024x1024 mandelbrot image at 100 iterations
SEQ, DECODE_BATCH, LU_BLOCK, STRIP, MAX_ITER = 2048, 4, 512, (256, 1024), 100
#: bf16 attention outputs against an f32 oracle: the bound tests/test_kernels.py
#: holds the bf16 kernels to (a few ulps of outputs of magnitude ~1).
BF16_ATTN_TOL = 4e-2
#: bmod on bf16-representable f32 operands: every product is exact in any MXU
#: pass count, so only f32 accumulation order over 512 terms differs.
BMOD_TOL = 1e-3
#: escape counts flip at the set's boundary under float-op reordering; the
#: share of pixels tests/test_kernels.py allows to differ.
MANDEL_MISMATCH_TOL = 5e-3
#: relative LU residual.  One bf16 rounding of every off-diagonal entry gives
#: about 4e-5 on this diagonally dominant matrix; one wrong 512x512 block
#: gives about 4e-3.
LU_RESIDUAL_TOL = 1e-3


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: Any, msg: str) -> None:
    print(f"[phase {phase}] {msg}", flush=True)


def _max_abs(a: jax.Array, b: jax.Array) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# phase 0
# ---------------------------------------------------------------------------
def phase_device(chips: int) -> Dict[str, Any]:
    devs = jax.devices()
    require(devs[0].platform == "tpu",
            f"phase 0: JAX found no TPU (first device: {devs[0].platform})")
    require(len(devs) >= chips,
            f"phase 0: {chips} chips asked for, JAX sees {len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(0, f"{info['kind']} x{info['count']}, jax {jax.__version__}")
    return info


# ---------------------------------------------------------------------------
# phase 1: the Pallas kernels, compiled
# ---------------------------------------------------------------------------
def phase_kernels(cfg, *, seq: int = SEQ, batch: int = DECODE_BATCH,
                  block: int = LU_BLOCK, strip: Tuple[int, int] = STRIP,
                  max_iter: int = MAX_ITER, seed: int = 0,
                  interpret: bool = False) -> None:
    """Each kernel at one real shape against its oracle: attention at the
    model's head layout (prefill of one ``seq``-token prompt, decode of
    ``batch`` ragged rows over a ``seq``-slot cache), one ``block``-square
    sparselu update, and one ``strip`` of a square mandelbrot image."""
    H, K, d = cfg.n_heads, cfg.n_kv, cfg.head_dim
    r = H // K
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf16 = jnp.bfloat16

    q = jax.random.normal(ks[0], (K, r, seq, d), bf16)
    k = jax.random.normal(ks[1], (K, seq, d), bf16)
    v = jax.random.normal(ks[2], (K, seq, d), bf16)
    out = jax.jit(functools.partial(flash_attention, causal=True,
                                    interpret=interpret))(q, k, v)
    err = _max_abs(out, jax.jit(attention_ref)(q, k, v))
    say(1, f"flash_attention causal BK={K} r={r} S={seq} d={d} bf16: "
           f"max|err| {err!r} <= tol {BF16_ATTN_TOL}")
    require(err <= BF16_ATTN_TOL, "phase 1: flash_attention error")

    BK = batch * K
    q = jax.random.normal(ks[3], (BK, r, d), bf16)
    kc = jax.random.normal(ks[4], (BK, seq, d), bf16)
    vc = jax.random.normal(ks[5], (BK, seq, d), bf16)
    lens = jax.random.randint(ks[6], (BK,), 1, seq + 1).at[0].set(seq)
    out = jax.jit(functools.partial(flash_decode, interpret=interpret))(
        q, kc, vc, lens)
    err = _max_abs(out, jax.jit(flash_decode_ref)(q, kc, vc, lens))
    say(1, f"flash_decode BK={BK} r={r} cache={seq} d={d} bf16, ragged "
           f"kv_len: max|err| {err!r} <= tol {BF16_ATTN_TOL}")
    require(err <= BF16_ATTN_TOL, "phase 1: flash_decode error")

    a = jax.random.normal(ks[7], (block, block), jnp.float32)
    l, u = (jax.random.normal(kk, (block, block), bf16).astype(jnp.float32)
            for kk in jax.random.split(ks[8]))
    out = jax.jit(functools.partial(bmod, interpret=interpret))(a, l, u)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(bmod_ref)(a, l, u)
    err = _max_abs(out, ref)
    say(1, f"block_lu.bmod {block}x{block} f32: max|err| {err!r} "
           f"<= tol {BMOD_TOL}")
    require(err <= BMOD_TOL, "phase 1: bmod error")

    h, w = strip
    img = mandelbrot_strip(h, w, max_iter=max_iter, row_offset=h,
                           total_height=w, interpret=interpret)
    ref = jax.jit(lambda: mandelbrot_ref(h, w, max_iter=max_iter,
                                         row_offset=h, total_height=w))()
    mismatch = float(jnp.mean(img != ref))
    say(1, f"mandelbrot strip rows {h}..{2 * h} of {w}x{w}, {max_iter} "
           f"iterations: pixels differing {mismatch!r} <= tol "
           f"{MANDEL_MISMATCH_TOL}")
    require(mismatch <= MANDEL_MISMATCH_TOL, "phase 1: mandelbrot mismatch")


# ---------------------------------------------------------------------------
# phase 2: BOTS sparselu through the runtime
# ---------------------------------------------------------------------------
def f32_matmul_precision() -> str:
    """What the default precision does to f32 matmul operands, observed."""
    x = jnp.full((128, 128), 1 + 2.0 ** -12, jnp.float32)
    got = float(jnp.max(jax.jit(jnp.matmul)(x, jnp.eye(128, dtype=jnp.float32))))
    kept = "rounded to bf16 (one MXU pass)" if got == 1.0 else "kept at f32"
    return f"default precision: operand 1+2^-12 {kept}"


def run_sparselu(rt: ClusterRuntime, mat: jax.Array, K: int, B: int, *,
                 peer: bool, policy: str = "locality"
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Factor ``mat`` on ``rt``; return (packed L\\U [K,K,B,B], raw results)."""
    res = rt.wavefront_offload(_build_dag(mat, K, B), nowait=True, peer=peer,
                               policy=policy, tag="sparselu")

    def final(i: int, j: int) -> jax.Array:
        if i == j:
            return res[f"lu0_{i}"]
        return res[f"fwd_{i}_{j}"] if i < j else res[f"bdiv_{j}_{i}"]

    # blocks come back from the devices that made them: gather on device 0
    dev0 = jax.devices()[0]
    packed = jnp.stack([jnp.stack([jax.device_put(final(i, j), dev0)
                                   for j in range(K)]) for i in range(K)])
    return packed, res


def _square(blocks: jax.Array) -> jax.Array:
    K, _, B, _ = blocks.shape
    return blocks.transpose(0, 2, 1, 3).reshape(K * B, K * B)


@jax.jit
def lu_residual(packed: jax.Array, mat: jax.Array) -> jax.Array:
    """||L.U - A||_F / ||A||_F, with L.U at full f32 precision."""
    P, A = _square(packed), _square(mat)
    L = jnp.tril(P, -1) + jnp.eye(P.shape[0], dtype=P.dtype)
    R = jnp.matmul(L, jnp.triu(P), precision=jax.lax.Precision.HIGHEST) - A
    return jnp.linalg.norm(R) / jnp.linalg.norm(A)


@jax.jit
def rel_diff(x: jax.Array, y: jax.Array, mat: jax.Array) -> jax.Array:
    return jnp.linalg.norm(x - y) / jnp.linalg.norm(mat)


def n_execs(rt: ClusterRuntime) -> int:
    return sum(1 for c in rt.pool.trace if c.op == "EXEC")


def phase_dag(*, K: int = 8, B: int = 512, devices: int = 4,
              seed: int = 0) -> Tuple[ClusterRuntime, int, jax.Array]:
    """Returns (runtime, EXECs expected, packed factors)."""
    mat = _matrix(K, B, seed)
    rt = ClusterRuntime(RuntimeConfig(n_virtual=devices, comm_mode="direct"),
                        table=_make_table(K))
    n_tasks = len(_build_dag(mat, K, B))
    t0 = time.perf_counter()
    packed, _ = run_sparselu(rt, mat, K, B, peer=True)
    jax.block_until_ready(packed)
    wall = time.perf_counter() - t0
    sds = jax.ShapeDtypeStruct((K, K, B, B), jnp.float32)
    serial = rt.target("sparselu_serial", 0,
                       MapSpec(to={"mat": mat}, from_={"out": sds}))["out"]
    res_dag = float(lu_residual(packed, mat))
    res_serial = float(lu_residual(serial, mat))
    diff = float(rel_diff(packed, serial, mat))
    say(2, f"sparselu K={K} B={B} f32 ({K * B}x{K * B}, {n_tasks} tasks) on "
           f"{devices} virtual devices, peer-routed, locality-placed; "
           f"first run incl. compiles {wall:.3f} s")
    say(2, f"relative residual ||LU-A||/||A||: DAG {res_dag!r}, "
           f"sparselu_serial {res_serial!r}, tol {LU_RESIDUAL_TOL}; "
           f"||DAG-serial||/||A|| {diff!r}; {f32_matmul_precision()}")
    require(res_dag <= LU_RESIDUAL_TOL, "phase 2: DAG residual")
    require(res_serial <= LU_RESIDUAL_TOL, "phase 2: serial residual")
    require(diff <= LU_RESIDUAL_TOL, "phase 2: DAG and serial disagree")
    return rt, n_tasks + 1, packed


# ---------------------------------------------------------------------------
# phase 3: serving through the runtime
# ---------------------------------------------------------------------------
def top2_margin(logits: jax.Array) -> Tuple[float, float]:
    """(top-1 logit, top-1 minus top-2) of a [V] logit row."""
    top = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
    return float(top[0]), float(top[0] - top[1])


def bf16_margin_tol(top1: float, n_layers: int) -> float:
    """One bf16 rounding of the top logit per layer: a first-token flip
    between two batch shapes is admissible only inside this margin."""
    ulp = 2.0 ** (np.floor(np.log2(max(abs(top1), 2.0 ** -30))) - 7)
    return float(n_layers * ulp)


def phase_serving(cfg, *, n_req: int = 4, prompt_len: int = 64,
                  new_tokens: int = 16, max_len: int = 256,
                  seed: int = 0) -> Tuple[ClusterRuntime, int]:
    """Returns (runtime, EXECs expected)."""
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    nbytes = sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params))
    say(3, f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
           f"{cfg.vocab}, {n_params} params ({nbytes} bytes "
           f"{cfg.param_dtype}) initialised in {time.perf_counter() - t0:.3f} s")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (n_req, prompt_len), dtype=np.int32)
    reqs = [Request(rid=i, prompt=prompts[i].tolist(), max_new_tokens=new_tokens)
            for i in range(n_req)]
    scfg = ServeConfig(batch=n_req, max_len=max_len)

    local_engine = ServeEngine(model, params, scfg)
    t0 = time.perf_counter()
    local = local_engine.serve(reqs)
    t_local = time.perf_counter() - t0
    rt = ClusterRuntime(RuntimeConfig(n_virtual=1))
    t0 = time.perf_counter()
    pooled = ServeEngine(model, params, scfg, runtime=rt).serve(reqs)
    t_pool = time.perf_counter() - t0
    say(3, f"{n_req} requests x {prompt_len}-token prompts, {new_tokens} new "
           f"tokens, max_len {max_len}, greedy: local continuous "
           f"{t_local:.3f} s, pool {t_pool:.3f} s (first runs, compiles "
           f"included)")
    for out, path in ((local, "local"), (pooled, "pool")):
        for r in reqs:
            got = len(out[r.rid].tokens) if r.rid in out else None
            require(got == new_tokens and not out[r.rid].timed_out,
                    f"phase 3: {path} request {r.rid} returned {got} tokens")

    # the local path's own prefill logits at each request's last position:
    # the same executable the engine ran (64 is a bucket length, no pads)
    logits, _, _ = local_engine._prefill_masked(
        params, {"tokens": jnp.asarray(prompts)},
        jnp.zeros((n_req,), jnp.int32))
    first_agree, later, later_eq = 0, 0, 0
    for i, r in enumerate(reqs):
        a, b = local[r.rid].tokens, pooled[r.rid].tokens
        top1, margin = top2_margin(logits[i, -1])
        tol = bf16_margin_tol(top1, cfg.n_layers)
        if a[0] == b[0]:
            first_agree += 1
        else:
            say(3, f"request {r.rid}: first tokens differ ({a[0]} vs {b[0]}); "
                   f"local top-2 margin {margin!r} < tol {tol!r} required")
            require(margin < tol, f"phase 3: request {r.rid} first token")
        later += len(a) - 1
        later_eq += sum(x == y for x, y in zip(a[1:], b[1:]))
    say(3, f"first generated token: {first_agree}/{n_req} agree pool vs "
           f"local; later tokens agree {later_eq}/{later} (not required: "
           f"batch shapes differ)")

    # pool mode pins the weights on the chip they already live on: settle
    # whether that aliases the caller's buffers or copies them
    rt.pool.sync()
    ent = rt.pool.present[0].get("_serve_params")
    store = rt.pool.devices[0].store
    leaves = jax.tree.leaves(params)
    aliased = sum(store.device_address(h).unsafe_buffer_pointer()
                  == x.unsafe_buffer_pointer()
                  for h, x in zip(ent.handles, leaves))
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(3, f"pool-resident weights alias the caller's buffers: {aliased}/"
           f"{len(leaves)} leaves; peak device memory "
           f"{'not reported' if peak is None else f'{peak} bytes'}")
    require(aliased == len(leaves), "phase 3: pool copied the weights")
    return rt, n_req * new_tokens      # one prefill + (new-1) decodes each


# ---------------------------------------------------------------------------
# phase 4
# ---------------------------------------------------------------------------
def phase_recovery(runs: Sequence[Tuple[str, ClusterRuntime, int]]) -> None:
    for name, rt, expected in runs:
        pool = rt.pool
        strikes = {d: pool.health.failures(d) for d in range(len(pool))
                   if pool.health.failures(d)}
        hedges = sum(1 for c in pool.trace if "~hedge" in c.tag)
        fallbacks = getattr(rt.transport, "fallbacks", 0)
        execs = n_execs(rt)
        say(4, f"{name}: failures absorbed {pool.failures_absorbed}, health "
               f"strikes {strikes or 0}, blacklist {sorted(pool.health.blacklist)}"
               f", transport fallbacks {fallbacks}, hedges {hedges}, deadline "
               f"timeouts {pool.straggler_timeouts or 0}, EXECs {execs} "
               f"(expected {expected})")
        require(pool.failures_absorbed == 0, f"phase 4: {name} absorbed failures")
        require(not strikes and not pool.health.blacklist,
                f"phase 4: {name} health strikes")
        require(fallbacks == 0, f"phase 4: {name} funnel fallbacks")
        require(hedges == 0, f"phase 4: {name} hedges")
        require(not pool.straggler_timeouts, f"phase 4: {name} timeouts")
        require(execs == expected, f"phase 4: {name} re-executed tasks")


# ---------------------------------------------------------------------------
# four chips: one NodeDevice per chip
# ---------------------------------------------------------------------------
def phase_four_chips(*, chips: int = 4, K: int = 8, B: int = 512,
                     seed: int = 0) -> None:
    """sparselu on a pool with one device per chip, peer- and funnel-routed,
    against phase 2's run on one chip split into ``chips`` virtual devices.

    Locality placement follows the DAG's single root and keeps every task on
    one device, so the chip pool places round-robin: every chip runs tasks
    and most edges cross chips."""
    mat = _matrix(K, B, seed)
    mesh = jax.make_mesh((chips,), ("node",))
    homes = list(mesh.devices.flat)
    base_rt = ClusterRuntime(RuntimeConfig(n_virtual=chips, comm_mode="direct"),
                             table=_make_table(K))
    base, _ = run_sparselu(base_rt, mat, K, B, peer=True)
    base_rt.shutdown()
    results = {"1 chip": base}
    for peer in (True, False):
        route = "peer" if peer else "funnel"
        pool = DevicePool.from_mesh_slices(mesh, "node", table=_make_table(K))
        rt = ClusterRuntime(RuntimeConfig(comm_mode="direct"), pool=pool)
        t0 = time.perf_counter()
        packed, res = run_sparselu(rt, mat, K, B, peer=peer,
                                   policy="round-robin")
        jax.block_until_ready(packed)
        wall = time.perf_counter() - t0
        # each task's output buffer lives on the chip of the device that ran
        # it (region tags end in the task name)
        ran_on = {c.tag.rsplit(":", 1)[1]: c.device
                  for c in pool.trace if c.op == "EXEC"}
        for name, value in res.items():
            require(value.devices() == {homes[ran_on[name]]},
                    f"{route}: {name}'s output is not on its device's chip")
        per_dev = [sum(1 for c in pool.trace
                       if c.op == "EXEC" and c.device == d)
                   for d in range(chips)]
        sends = sum(1 for c in pool.trace if c.op == "SEND")
        say("4-chip", f"{route}-routed, round-robin: EXECs per chip "
                      f"{per_dev}, {sends} chip-to-chip SENDs, all "
                      f"{len(res)} task outputs on the chip that ran them; "
                      f"first run incl. compiles {wall:.3f} s")
        require(all(per_dev), f"{route}: a chip ran no task")
        phase_recovery([(f"{chips}-chip {route}", rt, len(res))])
        rt.shutdown()
        results[route] = packed
    for name, packed in results.items():
        res_ = float(lu_residual(packed, mat))
        say("4-chip", f"{name}: relative residual {res_!r} <= tol "
                      f"{LU_RESIDUAL_TOL}")
        require(res_ <= LU_RESIDUAL_TOL, f"{name} residual")
    for x, y in (("peer", "funnel"), ("peer", "1 chip"), ("funnel", "1 chip")):
        diff = float(rel_diff(results[x], results[y], mat))
        exact = bool(jnp.array_equal(results[x], results[y]))
        say("4-chip", f"{x} vs {y}: ||diff||/||A|| {diff!r} <= tol "
                      f"{LU_RESIDUAL_TOL}, bit-identical {exact}")
        require(diff <= LU_RESIDUAL_TOL, f"{x} and {y} disagree")


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-device-per-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    say(0, f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phase_four_chips(chips=4, seed=args.seed)
    else:
        cfg = get_config(ARCH)
        phase_kernels(cfg, seed=args.seed)
        dag_rt, dag_execs, _ = phase_dag(seed=args.seed)
        dag_rt.shutdown()
        serve_rt, serve_execs = phase_serving(cfg, seed=args.seed)
        serve_rt.shutdown()
        phase_recovery([("sparselu", dag_rt, dag_execs),
                        ("serving", serve_rt, serve_execs)])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
