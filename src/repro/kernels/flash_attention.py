"""Pallas TPU kernel: flash decode attention (serving hot path).

One query token per sequence attends over a long KV cache (GQA layout).
Grid = (batch, kv_blocks); the kv axis is innermost so VMEM scratch carries
the online-softmax state (running max, normalizer, weighted accumulator)
across kv blocks — the cache is streamed HBM→VMEM exactly once.

Training/prefill attention uses the chunked jnp implementation in
models/layers.py (differentiable, remat-friendly); this kernel is the
inference-path counterpart with identical math (validated vs ref.decode_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KV_BLOCK = 512
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, out_ref,
                   m_scr, l_scr, acc_scr):
    s = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hkv, g, d = acc_scr.shape
    q = q_ref[0].astype(jnp.float32).reshape(hkv, g, d)     # [Hkv, G, D]
    k = k_ref[0].astype(jnp.float32)                        # [BS, Hkv, D]
    v = v_ref[0].astype(jnp.float32)
    length = len_ref[0, 0]

    logits = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)                  # [Hkv, G, BS]
    logits = logits / jnp.sqrt(jnp.float32(d))
    pos = s * k.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 2)
    logits = jnp.where(pos < length, logits, NEG_INF)

    m_prev = m_scr[...]                                      # [Hkv, G]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[..., None])                   # [Hkv, G, BS]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)                  # [Hkv, G, D]
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = (acc_scr[...] / denom).reshape(1, hkv * g, d)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "kv_block"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, *, interpret: bool,
                     kv_block: int = KV_BLOCK) -> jax.Array:
    """q: [B,H,D]; k/v: [B,S,Hkv,D]; length: [B] valid cache length."""
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    assert s % kv_block == 0, f"S={s} must be a multiple of kv_block={kv_block}"
    n_s = s // kv_block
    length2 = length.astype(jnp.int32).reshape(b, 1)

    return pl.pallas_call(
        _decode_kernel,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, kv_block, hkv, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, kv_block, hkv, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, length2)
