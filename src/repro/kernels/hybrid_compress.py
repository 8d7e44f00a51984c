"""Pallas TPU kernel: fused hybrid compress pass (paper Fig. 3 sender side).

One HBM read of the tensor produces, per ``(rows, 128)`` block of the
lane-dense layout (`kernels.tiling`):
  * kept values (full precision where |x| ≥ thr, else 0)
  * int8 sign plane (±1 where compressed, 0 where kept)
  * per-lane partials over the compressed set — count, Σ|x|, max|x| — in
    rows 0–2 of one ``(8, 128)`` f32 tile per block
The partials are folded into the (count, sum_abs, max_abs) scalars by XLA —
replacing five separate elementwise+reduce HLO passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling as T

_PART_ROWS = 8           # one f32 tile of partials per block


def _compress_kernel(x_ref, thr_ref, kept_ref, sign_ref, part_ref):
    x = x_ref[...]                                   # [R, 128] f32
    absx = jnp.abs(x)
    mask = absx < thr_ref[...]
    kept_ref[...] = jnp.where(mask, 0.0, x)
    sign_ref[...] = jnp.where(mask, jnp.sign(x), 0.0).astype(jnp.int8)
    comp = jnp.where(mask, absx, 0.0)
    cnt = jnp.sum(mask.astype(jnp.float32), axis=0, keepdims=True)
    ssum = jnp.sum(comp, axis=0, keepdims=True)
    smax = jnp.max(comp, axis=0, keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (_PART_ROWS, T.LANES), 0)
    part_ref[...] = jnp.where(row == 0, cnt,
                              jnp.where(row == 1, ssum,
                                        jnp.where(row == 2, smax, 0.0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def hybrid_compress(x: jax.Array, thr: jax.Array, *, interpret: bool):
    """Returns (kept, sign_i8, count, sum_abs, max_abs) — see ref.hybrid_compress."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = T.block_rows(n)
    # Pad with +inf so padding is never "compressed" (|inf| ≥ thr always).
    tiles = T.to_tiles(flat, rows, jnp.inf)
    n_blocks = tiles.shape[0] // rows

    kept, sign, part = pl.pallas_call(
        _compress_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rows, T.LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, T.LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, T.LANES), lambda i: (i, 0)),
            pl.BlockSpec((_PART_ROWS, T.LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
            jax.ShapeDtypeStruct(tiles.shape, jnp.int8),
            jax.ShapeDtypeStruct((n_blocks * _PART_ROWS, T.LANES),
                                 jnp.float32),
        ],
        interpret=interpret,
        name="hybrid_compress",
    )(tiles, thr.astype(jnp.float32).reshape(1, 1))

    kept = T.from_tiles(kept, n).reshape(shape).astype(dtype)
    sign = T.from_tiles(sign, n).reshape(shape)
    part = part.reshape(n_blocks, _PART_ROWS, T.LANES)
    # per-lane counts are exact in f32 (each ≤ rows); sum them as int32
    count = jnp.sum(part[:, 0].astype(jnp.int32))
    sum_abs = jnp.sum(part[:, 1])
    max_abs = jnp.max(part[:, 2])
    return kept, sign, count, sum_abs, max_abs
