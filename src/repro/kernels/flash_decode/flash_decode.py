"""Flash-decode Pallas TPU kernel: one-token attention against a KV cache.

The decode-attention hot spot is HBM-bandwidth-bound: the whole cache
[S, d] must stream through VMEM once per generated token while compute is a
rank-1 contraction.  TPU-native design: grid (batch·kv_heads, S/block_kv)
with the cache dimension sequential; the online-softmax state (o, m, l) for
all r = H/K query rows of the group lives in VMEM scratch, so HBM traffic is
exactly one cache read per token — the roofline minimum.  The per-row valid
lengths ``kv_len`` [BK] arrive as a scalar-prefetch operand: the whole vector
sits in SMEM and each grid row reads its own entry to mask the tail block.

On a `kv-model`-sharded cache the same kernel runs per shard and the partial
(o, m, l) combine is the psum the SPMD partitioner inserts — this kernel is
the per-shard body of the flash-decoding pattern the §Perf bonus sweep
measured.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, sm_scale: float, block_kv: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                    # [r, d]
    k = k_ref[0]                                    # [bk, d]
    v = v_ref[0]
    kv_len = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot_general(p.astype(v.dtype), v,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 kv_len: jax.Array, *, block_kv: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: [BK, r, d]; k_cache/v_cache: [BK, S, d]; kv_len: [BK] int32.

    Returns [BK, r, d] — attention of each group's r query heads over the
    first ``kv_len[b]`` cache rows.
    """
    BK, r, d = q.shape
    S = k_cache.shape[1]
    block_kv = min(block_kv, S)
    while S % block_kv:
        block_kv //= 2
    nk = S // block_kv
    sm_scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_kv=block_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # kv_len -> SMEM
        grid=(BK, nk),
        in_specs=[
            pl.BlockSpec((1, r, d), lambda b, j, lens: (b, 0, 0)),  # q
            pl.BlockSpec((1, block_kv, d), lambda b, j, lens: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, lens: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, d), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((r, d), jnp.float32),     # acc
            pltpu.VMEM((r,), jnp.float32),       # m
            pltpu.VMEM((r,), jnp.float32),       # l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, r, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ) if not interpret else None,
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32), q, k_cache, v_cache)
