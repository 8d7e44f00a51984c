"""Matrix FLOPs of a function, counted from its shapes.

One generic counter for every configuration: it traces the function to a
jaxpr and adds ``2 * output elements * contraction size`` for each
``conv_general_dilated`` and ``dot_general`` it finds, also inside nested
jaxprs (jit, scan, custom derivatives). Elementwise work, normalisations
and reductions are not counted: a model's FLOPs are its multiply-adds.
"""
from __future__ import annotations

import math

import jax


def _conv_flops(eqn) -> int:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    # rhs spec: (out feature dim, in feature dim, *spatial dims)
    rhs_in = rhs.shape[dn.rhs_spec[1]]
    rhs_spatial = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
    del lhs
    return 2 * math.prod(out.shape) * rhs_in * rhs_spatial


def _dot_flops(eqn) -> int:
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    k = math.prod(lhs.shape[d] for d in lhs_contract)
    return 2 * math.prod(out.shape) * k


def _subjaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):   # ClosedJaxpr
                yield x.jaxpr, 1
            elif hasattr(x, "eqns"):                           # Jaxpr
                yield x, 1


def jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "dot_general":
            total += _dot_flops(eqn)
        reps = eqn.params.get("length", 1) if name == "scan" else 1
        for sub, _ in _subjaxprs(eqn):
            total += reps * jaxpr_flops(sub)
    return total


def flops(fn, *args) -> int:
    """Matrix FLOPs of one call of ``fn`` on arguments shaped like ``args``
    (arrays or ``jax.ShapeDtypeStruct``)."""
    return jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)


def forward_flops_per_sample(apply, params, sample_shape) -> int:
    """Forward FLOPs of ``apply(params, x)`` for one sample."""
    x = jax.ShapeDtypeStruct((1,) + tuple(sample_shape), "float32")
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    return flops(apply, p, x)
