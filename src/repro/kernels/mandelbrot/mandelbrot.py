"""Mandelbrot escape-time Pallas kernel — the paper's §5.4 workload.

The paper offloads image *strips* to cluster devices; this kernel computes one
strip tile per grid step.  Escape iteration is VPU work (elementwise complex
arithmetic over a [block_h, W] tile); the iteration count is a static bound
with the escape condition folded in via masking, which keeps the loop shape
static for Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mandel_kernel(o_ref, *, x0: float, dx: float, y0: float, dy: float,
                   width: int, max_iter: int, block_h: int):
    ih = pl.program_id(0)
    rows = ih * block_h + jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 1)
    cx = x0 + cols.astype(jnp.float32) * dx
    cy = y0 + rows.astype(jnp.float32) * dy

    # An escaped point's z is frozen with |z|^2 > 4, so the escape test on
    # the carried z alone says whether the point is still iterating.
    def body(_, state):
        zx, zy, count = state
        zx2, zy2 = zx * zx, zy * zy
        alive = zx2 + zy2 <= 4.0
        zx, zy = (jnp.where(alive, zx2 - zy2 + cx, zx),
                  jnp.where(alive, 2.0 * zx * zy + cy, zy))
        return zx, zy, count + alive.astype(jnp.int32)

    # Mosaic gives a loop carry the vector layout of its initial value.  A
    # constant (or cx / cy alone, each invariant along one axis) gets a
    # replicated layout that the body's output cannot be relaid into, so the
    # zero carries are derived from a grid that varies along both axes.
    z0 = 0.0 * (cx + cy)
    _, _, count = jax.lax.fori_loop(
        0, max_iter, body, (z0, z0, z0.astype(jnp.int32)))
    o_ref[...] = count


def mandelbrot(height: int, width: int, *, xmin: float = -2.0,
               xmax: float = 0.6, ymin: float = -1.3, ymax: float = 1.3,
               max_iter: int = 100, block_h: int = 64,
               row_offset: int = 0, total_height: int = 0,
               interpret: bool = False) -> jax.Array:
    """Escape-time counts [height, width] (int32).

    ``row_offset/total_height`` let a strip render its slice of a larger
    image (the paper's per-device strips): rows are global indices.
    """
    th = total_height or height
    bh = min(block_h, height)
    while height % bh:
        bh -= 1
    # global pixel grid steps; local row 0 = global row `row_offset`
    dy = (ymax - ymin) / (th - 1)
    dx = (xmax - xmin) / (width - 1)
    kernel = functools.partial(
        _mandel_kernel, x0=xmin, dx=dx, y0=ymin + row_offset * dy, dy=dy,
        width=width, max_iter=max_iter, block_h=bh)
    return pl.pallas_call(
        kernel,
        grid=(height // bh,),
        out_specs=pl.BlockSpec((bh, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        interpret=interpret,
    )()
