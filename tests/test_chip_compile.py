"""Compile phase 1's Pallas kernels for a TPU v5e chip that is described,
not attached, at the shapes chip_smoke.py runs them.

Interpret-mode tests cannot see what Mosaic refuses (unaligned block shapes,
vector relayouts); these compiles can.  Nothing runs, so no result is
checked.  The topology is described inside a fixture so that importing this
file never loads the TPU library.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from chip_smoke import ARCH, DECODE_BATCH, LU_BLOCK, MAX_ITER, SEQ, STRIP  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.block_lu.block_lu import bmod  # noqa: E402
from repro.kernels.flash_attention.flash_attention import flash_attention  # noqa: E402
from repro.kernels.flash_decode.flash_decode import flash_decode  # noqa: E402
from repro.kernels.mandelbrot.mandelbrot import mandelbrot  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def heads():
    cfg = get_config(ARCH)
    return cfg.n_heads // cfg.n_kv, cfg.n_kv, cfg.head_dim


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Mosaic kernel
    return compiled


def test_flash_attention_compiles(one_chip, heads):
    r, K, d = heads
    sds = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                            sharding=one_chip)
    _compile(functools.partial(flash_attention, causal=True),
             sds((K, r, SEQ, d)), sds((K, SEQ, d)), sds((K, SEQ, d)))


def test_flash_decode_compiles(one_chip, heads):
    r, K, d = heads
    BK = DECODE_BATCH * K
    sds = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                            sharding=one_chip)
    _compile(flash_decode, sds((BK, r, d)), sds((BK, SEQ, d)),
             sds((BK, SEQ, d)),
             jax.ShapeDtypeStruct((BK,), jnp.int32, sharding=one_chip))


def test_bmod_compiles(one_chip):
    blk = jax.ShapeDtypeStruct((LU_BLOCK, LU_BLOCK), jnp.float32,
                               sharding=one_chip)
    _compile(bmod, blk, blk, blk)


def test_mandelbrot_compiles(one_chip):
    h, w = STRIP
    _compile(lambda: mandelbrot(h, w, max_iter=MAX_ITER, row_offset=h,
                                total_height=w), out_shardings=one_chip)
