"""Pallas TPU kernel: magnitude histogram for O(n) top-k threshold selection.

Top-K on TPU is realized as a magnitude threshold (DESIGN.md §3). Selecting the
threshold by sort is O(n log n) and HBM-traffic heavy; this kernel computes a
256-bin histogram of |x|/max in one HBM pass over the lane-dense
``(rows, 128)`` layout of `kernels.tiling`, from which the (jnp) cumsum picks
the bin edge at the target sparsity.

The selected edge is the LOWER edge of the bin whose cdf first reaches
ratio·n: compression masks use strict ``|x| < thr``, so the lower edge keeps
ratio=0 exactly lossless (thr=0) and matches
``core.compression.magnitude_threshold``'s strict-< semantics to within one
bin width at every ratio.

Scatter is not VPU-friendly, so binning is a compare-and-count per bin: each
grid step adds, for every bin, the per-lane count of its block's elements in
that bin to a resident ``[N_BINS, 128]`` accumulator (8 bins = one f32 tile
per store). The 128 lanes are summed by XLA afterwards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling as T

N_BINS = 256
_SUBLANES = 8            # f32 sublanes per tile: bins written per store


def _hist_kernel(x_ref, scale_ref, hist_ref):
    """Grid: (n_blocks,). hist_ref [N_BINS, 128] holds per-lane counts."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    mag = jnp.abs(x_ref[...])                                # [R, 128]
    idx = jnp.clip((mag * scale_ref[...]).astype(jnp.int32), 0, N_BINS - 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, T.LANES), 0)

    def bin_group(g, carry):
        base = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        acc = jnp.zeros((_SUBLANES, T.LANES), jnp.float32)
        for j in range(_SUBLANES):
            cnt = jnp.sum((idx == base + j).astype(jnp.float32), axis=0,
                          keepdims=True)                     # [1, 128]
            acc = jnp.where(sub == j, cnt, acc)
        hist_ref[pl.ds(base, _SUBLANES), :] += acc
        return carry

    jax.lax.fori_loop(0, N_BINS // _SUBLANES, bin_group, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def magnitude_histogram(x: jax.Array, max_abs: jax.Array, *,
                        interpret: bool) -> jax.Array:
    """256-bin histogram of |x| over [0, max_abs]. Pads with zeros, which
    land in bin 0 and are subtracted afterwards."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = T.block_rows(n)
    tiles = T.to_tiles(flat, rows, 0.0)
    pad = tiles.size - n
    scale = (N_BINS / jnp.maximum(max_abs, 1e-30)).astype(
        jnp.float32).reshape(1, 1)

    lanes = pl.pallas_call(
        _hist_kernel,
        grid=(tiles.shape[0] // rows,),
        in_specs=[
            pl.BlockSpec((rows, T.LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N_BINS, T.LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N_BINS, T.LANES), jnp.float32),
        interpret=interpret,
        name="magnitude_histogram",
    )(tiles, scale)
    # per-lane counts are exact in f32 (each ≤ rows < 2^24); sum as int32
    hist = jnp.sum(lanes.astype(jnp.int32), axis=1)
    return hist.at[0].add(-pad)                     # remove padding zeros


def threshold(x: jax.Array, ratio: jax.Array, *,
              interpret: bool) -> jax.Array:
    """Full two-pass threshold: max-reduce (XLA) + histogram (Pallas) + cdf.

    Returns the LOWER edge of the bin whose cdf first reaches ratio·n, so
    ratio=0 yields thr=0 (strict ``|x| < thr`` compresses nothing) and the
    result is within one bin width of ``jnp.quantile(|x|, ratio)``.
    """
    from repro.kernels import ref
    max_abs = jnp.max(jnp.abs(x))
    hist = magnitude_histogram(x, max_abs, interpret=interpret)
    return ref.threshold_from_histogram(hist, max_abs, ratio)
