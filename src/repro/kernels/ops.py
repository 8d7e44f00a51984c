"""Public jit'd wrappers for the Pallas kernels.

Every call names ``interpret`` explicitly: ``False`` compiles to Mosaic (TPU
only), ``True`` runs the Pallas interpreter. Nothing here picks a mode from
the platform. Hot-path callers should not use these wrappers directly — the
round engine goes through ``core.compression``'s ``fused_*`` operators, whose
backend switch ("pallas" | "interpret" | "jnp", DESIGN.md §4) is resolved once
per simulation and picks between these kernels and their pure-jnp twins in
``kernels.ref``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import hybrid_compress as _hc
from repro.kernels import recover as _rc
from repro.kernels import topk_threshold as _tt


def topk_threshold(x: jax.Array, ratio: jax.Array, *,
                   interpret: bool) -> jax.Array:
    """Magnitude threshold compressing ≈ratio·n smallest elements (O(n))."""
    return _tt.threshold(x, ratio, interpret=interpret)


def magnitude_histogram(x: jax.Array, max_abs: jax.Array, *,
                        interpret: bool) -> jax.Array:
    return _tt.magnitude_histogram(x, max_abs, interpret=interpret)


def hybrid_compress(x: jax.Array, thr: jax.Array, *, interpret: bool):
    """(kept, sign_i8, count, sum_abs, max_abs) — fused Fig.3 sender pass."""
    return _hc.hybrid_compress(x, thr, interpret=interpret)


def recover(kept, sign, local, mean_abs, max_abs, *, interpret: bool):
    """Fused Fig.3 receiver pass."""
    return _rc.recover(kept, sign, local, mean_abs, max_abs,
                       interpret=interpret)


def hybrid_roundtrip(x: jax.Array, local: jax.Array, ratio: jax.Array, *,
                     interpret: bool):
    """Kernel-path compress→recover (mirrors core.compression.hybrid_roundtrip)."""
    thr = topk_threshold(x, ratio, interpret=interpret)
    kept, sign, count, sum_abs, max_abs = hybrid_compress(x, thr,
                                                          interpret=interpret)
    mean_abs = sum_abs / jnp.maximum(count, 1)
    out = recover(kept, sign, local, mean_abs, max_abs, interpret=interpret)
    bits = (x.size - count) * 32 + count * 1 + 64
    return out, bits


def decode_attention(q, k, v, length, *, interpret: bool,
                     kv_block: int = _fa.KV_BLOCK):
    return _fa.decode_attention(q, k, v, length, interpret=interpret,
                                kv_block=kv_block)
