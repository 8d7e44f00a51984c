"""Track B: datacenter cohort-mode Caesar (DESIGN.md §2).

Pods are clients: the cross-pod reduction (DCN — the expensive link) is the
"WiFi" that Caesar compresses. Each pod runs τ local SGD steps from a
*recovered* initial model (staleness-aware download deviation), derives its
local delta, sparsifies it (importance-ranked upload ratio + optional error
feedback), and the compressed deltas cross the pod axis via an explicit pmean
inside a partial-manual shard_map over {"pod"}. Within a pod everything is
GSPMD (FSDP over "data", TP/EP over "model").

Per-pod persistent state (the cohort's stale local model, EF buffers) carries
a leading [n_pods] axis sharded over "pod". On a single-pod mesh the same
step runs without the pod shard_map (cohort = whole mesh); the compression
deviation is still applied, so convergence semantics match Track A.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compression as C
from repro.models import model as M
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class DistConfig:
    theta_d: float = 0.3          # this round's download ratio (from plan)
    theta_u: float = 0.35         # this round's upload ratio (from plan)
    server_lr: float = 1.0
    local_lr: float = 1e-2
    use_error_feedback: bool = False
    simulate_download: bool = True   # keep prev-params buffer + recovery path
    compressed_collective: bool = False  # beyond-paper: bf16 delta pmean
    prev_int8: bool = False          # beyond-paper: int8 stale-model buffer
                                     # (absmax-scaled; recovery reference only)
    backend: str = "auto"            # fused-operator backend (DESIGN.md §4)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any                   # global model
    prev_params: Optional[Any]    # [n_pods, ...] cohort-local stale models
    ef: Optional[Any]             # [n_pods, ...] error-feedback buffers
    step: jax.Array
    theta_d: jax.Array            # per-round scalars from the Caesar plan
    theta_u: jax.Array


def _quantize_leaf(a):
    scale = (jnp.max(jnp.abs(a.astype(jnp.float32))) / 127.0 + 1e-12)
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / scale), -127, 127)
    return {"q": q.astype(jnp.int8), "s": scale.astype(jnp.float32)}


def _dequantize_leaf(d, dtype):
    return (d["q"].astype(jnp.float32) * d["s"]).astype(dtype)


def _is_qleaf(x):
    return isinstance(x, dict) and set(x) == {"q", "s"}


def quantize_tree(tree):
    return jax.tree.map(_quantize_leaf, tree)


def dequantize_tree(qtree, like):
    return jax.tree.map(lambda d, l: _dequantize_leaf(d, l.dtype),
                        qtree, like, is_leaf=_is_qleaf)


def _n_pods(mesh) -> int:
    return mesh.shape["pod"] if (mesh is not None
                                 and "pod" in mesh.axis_names) else 1


def init_state(params, dcfg: DistConfig, mesh=None) -> TrainState:
    np_ = _n_pods(mesh)

    def rep(a):
        return jnp.broadcast_to(a[None], (np_,) + a.shape)

    if dcfg.simulate_download:
        prev = quantize_tree(params) if dcfg.prev_int8 else params
        prev = jax.tree.map(rep, prev)
    else:
        prev = None
    return TrainState(
        params=params,
        prev_params=prev,
        ef=jax.tree.map(lambda a: jnp.zeros((np_,) + a.shape, a.dtype),
                        params) if dcfg.use_error_feedback else None,
        step=jnp.zeros((), jnp.int32),
        theta_d=jnp.asarray(dcfg.theta_d, jnp.float32),
        theta_u=jnp.asarray(dcfg.theta_u, jnp.float32),
    )


def state_specs(cfg: ModelConfig, dcfg: DistConfig, mesh) -> TrainState:
    pspecs = M.param_specs(cfg, mesh)
    pod = "pod" if (mesh is not None and "pod" in mesh.axis_names) else None

    def podded(s):
        return P(pod, *s)

    if dcfg.simulate_download:
        if dcfg.prev_int8:
            prev_specs = jax.tree.map(
                lambda sp: {"q": podded(sp), "s": P(pod)}, pspecs)
        else:
            prev_specs = jax.tree.map(podded, pspecs)
    else:
        prev_specs = None
    return TrainState(
        params=pspecs,
        prev_params=prev_specs,
        ef=jax.tree.map(podded, pspecs) if dcfg.use_error_feedback else None,
        step=P(), theta_d=P(), theta_u=P())


# ---------------------------------------------------------------------------
# Per-leaf compression through the SAME fused operator layer as the Track-A
# round engine (core.compression.fused_*): O(n) histogram thresholds + fused
# compress/recover, with the backend resolved once per train-step build.
# Leaves stay separate (flattening across leaves would fight sharding).
# ---------------------------------------------------------------------------

def _leaf_hybrid_roundtrip(x, local, ratio, backend):
    xf = x.astype(jnp.float32)
    rec, _ = C.fused_hybrid_roundtrip(xf, local.astype(jnp.float32), ratio,
                                      backend)
    return rec.astype(local.dtype)


def _leaf_topk(x, ratio, backend):
    sparse, _ = C.fused_topk(x, ratio, backend)
    return sparse


def tree_download_recover(params, prev, ratio, backend: str = "jnp"):
    return jax.tree.map(
        lambda g, l: _leaf_hybrid_roundtrip(g, l, ratio, backend),
        params, prev)


def tree_upload_compress(delta, ef, ratio, backend: str = "jnp",
                         wire_dtype=None):
    """Returns (sparse_delta_in_wire_format, new_ef).

    ``wire_dtype`` (e.g. bf16 for ``compressed_collective``) is applied
    BEFORE the error-feedback residual is computed: EF must see exactly what
    the wire carries — sparsification loss *and* quantization loss — or it
    silently corrects only the former and the bf16 rounding error compounds
    round over round.
    """
    def to_wire(s):
        return s.astype(wire_dtype) if wire_dtype is not None else s

    if ef is None:
        sparse = jax.tree.map(lambda d: _leaf_topk(d, ratio, backend), delta)
        return jax.tree.map(to_wire, sparse), None
    corrected = jax.tree.map(lambda d, e: d + e.astype(d.dtype), delta, ef)
    sparse = jax.tree.map(lambda d: _leaf_topk(d, ratio, backend), corrected)
    wire = jax.tree.map(to_wire, sparse)
    new_ef = jax.tree.map(lambda c, w: (c - w.astype(c.dtype)).astype(c.dtype),
                          corrected, wire)
    return wire, new_ef


# ---------------------------------------------------------------------------
# One cohort round (runs either globally or inside the pod-manual region)
# ---------------------------------------------------------------------------

def _cohort_round(params, prev, ef, batch, theta_d, theta_u,
                  cfg: ModelConfig, dcfg: DistConfig, mesh, manual_axes=(),
                  backend: str = "jnp"):
    # (1) download: recover a precise initial model from the stale local copy
    if dcfg.simulate_download and prev is not None:
        local_ref = (dequantize_tree(prev, params) if dcfg.prev_int8
                     else prev)
        w_init = tree_download_recover(params, local_ref, theta_d, backend)
    else:
        w_init = params

    # (2) τ local SGD steps over microbatch slices
    tau = max(cfg.local_iters, 1)

    def micro(i):
        def slc(a):
            sz = a.shape[0] // tau
            return jax.lax.dynamic_slice_in_dim(a, i * sz, sz, axis=0)
        return jax.tree.map(slc, batch)

    def sgd_step(p, i):
        loss, g = jax.value_and_grad(M.loss_fn)(p, micro(i), cfg, mesh,
                                                manual_axes)
        newp = jax.tree.map(
            lambda a, b: (a - dcfg.local_lr * b).astype(a.dtype), p, g)
        return newp, loss

    w_fin, losses = jax.lax.scan(sgd_step, w_init, jnp.arange(tau))

    # (3) local delta in model dtype; (4) upload sparsification (+EF);
    # the bf16 wire cast happens INSIDE the compressor so the EF residual
    # is computed against the wire-format delta, not the pre-cast one
    sparse_wire = jnp.bfloat16 if dcfg.compressed_collective else None
    delta = jax.tree.map(lambda a, b: (a - b).astype(a.dtype), w_init, w_fin)
    sparse, new_ef = tree_upload_compress(delta, ef, theta_u, backend,
                                          wire_dtype=sparse_wire)
    new_prev = quantize_tree(w_fin) if dcfg.prev_int8 else w_fin
    return sparse, new_prev, new_ef, jnp.mean(losses)


def make_train_step(cfg: ModelConfig, dcfg: DistConfig, mesh):
    """Builds the jit-able Caesar-round train_step(state, batch)."""
    has_pod = mesh is not None and "pod" in mesh.axis_names
    backend = C.resolve_backend(dcfg.backend)   # once per step build

    def train_step(state: TrainState, batch):
        if has_pod:
            def per_pod(params, prev, ef, batch_l, theta_d, theta_u):
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                ex = lambda t: jax.tree.map(lambda a: a[None], t)
                sparse, w_fin, new_ef, loss = _cohort_round(
                    params, sq(prev) if prev is not None else None,
                    sq(ef) if ef is not None else None,
                    batch_l, theta_d, theta_u, cfg, dcfg, mesh,
                    manual_axes=("pod",), backend=backend)
                # (5) compressed deltas cross the pod axis (the "WiFi")
                agg = jax.tree.map(lambda d: jax.lax.pmean(d, "pod"), sparse)
                return (agg, ex(w_fin),
                        ex(new_ef) if new_ef is not None else None,
                        jax.lax.pmean(loss, "pod"))

            rep = lambda t: jax.tree.map(lambda _: P(), t)
            podded = lambda t: jax.tree.map(lambda _: P("pod"), t)
            agg, w_fin, new_ef, loss = jax.shard_map(
                per_pod, mesh=mesh,
                in_specs=(rep(state.params), podded(state.prev_params),
                          podded(state.ef), podded(batch), P(), P()),
                out_specs=(rep(state.params), podded(state.prev_params),
                           podded(state.ef), P()),
                axis_names={"pod"}, check_vma=False,
            )(state.params, state.prev_params, state.ef, batch,
              state.theta_d, state.theta_u)
        else:
            sparse, w_fin1, new_ef1, loss = _cohort_round(
                state.params,
                jax.tree.map(lambda a: a[0], state.prev_params)
                if state.prev_params is not None else None,
                jax.tree.map(lambda a: a[0], state.ef)
                if state.ef is not None else None,
                batch, state.theta_d, state.theta_u, cfg, dcfg, mesh,
                backend=backend)
            agg = sparse
            w_fin = jax.tree.map(lambda a: a[None], w_fin1)
            new_ef = (jax.tree.map(lambda a: a[None], new_ef1)
                      if new_ef1 is not None else None)

        # (6) server update
        new_params = jax.tree.map(
            lambda p, d: (p - dcfg.server_lr
                          * d.astype(jnp.float32)).astype(p.dtype),
            state.params, agg)
        new_state = TrainState(
            params=new_params,
            prev_params=w_fin if dcfg.simulate_download else None,
            ef=new_ef,
            step=state.step + 1,
            theta_d=state.theta_d, theta_u=state.theta_u)
        return new_state, {"loss": loss}

    return train_step


# ---------------------------------------------------------------------------
# Serving steps (no Caesar on the serving path)
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, mesh):
    def serve_step(params, cache, tokens, length):
        return M.decode_step(params, cache, {"tokens": tokens}, length, cfg,
                             mesh)
    return serve_step


def make_prefill(cfg: ModelConfig, mesh):
    def prefill_step(params, batch):
        return M.prefill(params, batch, cfg, mesh)
    return prefill_step
