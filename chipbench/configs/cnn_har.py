"""Plain reference of CNN-H, the Caesar paper's HAR model.

Input windows of 128 steps x 9 channels; three 1-D convolutions of kernel
5 and stride 2 (32, 64, 64 channels), each followed by a parameter-free
per-sample normalisation over time and a ReLU; then a dense layer of 128
and the 6-class head: 164,134 parameters. Weights are He-normal, drawn in
the order given below from one key split 6 ways.

``apply`` runs every convolution and matmul in float32 at the given
precision (``HIGHEST`` unless told otherwise); ``operand_dtype`` rounds
their inputs to a narrower type first (the control's float8), still
accumulating in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape) * (2.0 / fan_in) ** 0.5


def init(key, n_classes: int = 6):
    ks = jax.random.split(key, 6)
    return {"c1": _he(ks[0], (5, 9, 32), 45),
            "c2": _he(ks[1], (5, 32, 64), 160),
            "c3": _he(ks[2], (5, 64, 64), 320),
            "f1_w": _he(ks[3], (64 * 16, 128), 64 * 16),
            "f1_b": jnp.zeros(128),
            "f2_w": _he(ks[4], (128, n_classes), 128),
            "f2_b": jnp.zeros(n_classes)}


def _round(a, dt):
    """``a`` rounded to the values ``dt`` can hold (kept in float32)."""
    return a if dt is None else a.astype(dt).astype(jnp.float32)


def _conv(x, w, precision, dt):
    return jax.lax.conv_general_dilated(
        _round(x, dt), _round(w, dt), (2,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC"), precision=precision)


def _norm(x):
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.var(x, axis=1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5)


def apply(p, x, precision=jax.lax.Precision.HIGHEST, operand_dtype=None):
    h = x
    for name in ("c1", "c2", "c3"):
        h = jax.nn.relu(_norm(_conv(h, p[name], precision, operand_dtype)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(_round(h, operand_dtype),
                            _round(p["f1_w"], operand_dtype),
                            precision=precision) + p["f1_b"])
    return jnp.dot(_round(h, operand_dtype), _round(p["f2_w"], operand_dtype),
                   precision=precision) + p["f2_b"]
