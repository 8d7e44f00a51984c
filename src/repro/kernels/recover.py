"""Pallas TPU kernel: fused model recovery (paper Fig. 3 receiver side).

Elementwise over (kept, sign, local) with two broadcast scalars — one fused
HBM pass instead of the ~6-op XLA chain (sign-compare, abs-compare, two
selects, scale, merge). The int8 sign plane is read as int8, in the same
lane-dense ``(rows, 128)`` layout as the float planes (`kernels.tiling`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling as T


def _recover_kernel(kept_ref, sign_ref, local_ref, mean_ref, max_ref,
                    out_ref):
    kept = kept_ref[...]
    sgn = sign_ref[...].astype(jnp.float32)
    local = local_ref[...]
    mask = sgn != 0.0
    sign_bad = jnp.sign(local) * sgn < 0.0
    mag_bad = jnp.abs(local) > max_ref[...]
    approx = jnp.where(sign_bad | mag_bad, sgn * mean_ref[...], local)
    out_ref[...] = jnp.where(mask, approx, kept)


@functools.partial(jax.jit, static_argnames=("interpret",))
def recover(kept: jax.Array, sign: jax.Array, local: jax.Array,
            mean_abs: jax.Array, max_abs: jax.Array, *,
            interpret: bool) -> jax.Array:
    shape, dtype = local.shape, local.dtype
    n = kept.size
    rows = T.block_rows(n)

    def tile(a, dt):
        return T.to_tiles(a.reshape(-1).astype(dt), rows, 0)

    def scalar(s):
        return s.astype(jnp.float32).reshape(1, 1)

    tiles = tile(kept, jnp.float32)
    block = pl.BlockSpec((rows, T.LANES), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        _recover_kernel,
        grid=(tiles.shape[0] // rows,),
        in_specs=[block, block, block, one, one],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
        name="recover",
    )(tiles, tile(sign, jnp.int8), tile(local, jnp.float32),
      scalar(mean_abs), scalar(max_abs))
    return T.from_tiles(out, n).reshape(shape).astype(dtype)
