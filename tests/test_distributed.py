"""Track-B cohort-mode tests.

In-process tests run on a 1×1 mesh (the same code paths — shard_map, specs,
compression — with axis sizes 1). A subprocess test exercises a real
2×2×2 multi-pod mesh via xla_force_host_platform_device_count (jax locks the
device count at first init, so it must be a fresh interpreter).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.fl import distributed as D
from repro.launch.mesh import make_local_mesh
from repro.models import model as M


def _smoke_setup(arch="qwen1p5_4b", tau=2):
    cfg = dataclasses.replace(configs.get(arch).smoke(), local_iters=tau)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    return cfg, params, batch


def test_train_step_runs_and_loss_finite():
    cfg, params, batch = _smoke_setup()
    dcfg = D.DistConfig(theta_d=0.3, theta_u=0.4, local_lr=1e-2)
    state = D.init_state(params, dcfg, mesh=None)
    step = D.make_train_step(cfg, dcfg, mesh=None)
    state2, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1
    # params actually moved
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(state2.params),
                                jax.tree.leaves(state.params)))
    assert delta > 0


def test_loss_decreases_over_rounds():
    cfg, params, batch = _smoke_setup(tau=4)
    dcfg = D.DistConfig(theta_d=0.2, theta_u=0.3, local_lr=5e-2)
    state = D.init_state(params, dcfg, mesh=None)
    step = jax.jit(D.make_train_step(cfg, dcfg, mesh=None))
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_compression_ratio_zero_matches_uncompressed_sgd():
    """θ_u=0, θ_d=0, fresh prev ⇒ Caesar round == plain local SGD."""
    cfg, params, batch = _smoke_setup(tau=1)
    dcfg = D.DistConfig(theta_d=0.0, theta_u=0.0, local_lr=1e-2)
    state = D.init_state(params, dcfg, mesh=None)
    step = jax.jit(D.make_train_step(cfg, dcfg, mesh=None))
    s2, _ = step(state, batch)

    lr = 1e-2
    g = jax.grad(M.loss_fn)(params, batch, cfg)
    expect = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    for a, b in zip(jax.tree.leaves(s2.params), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


def test_error_feedback_accumulates():
    cfg, params, batch = _smoke_setup(tau=1)
    dcfg = D.DistConfig(theta_u=0.9, use_error_feedback=True)
    state = D.init_state(params, dcfg, mesh=None)
    step = jax.jit(D.make_train_step(cfg, dcfg, mesh=None))
    s2, _ = step(state, batch)
    ef_norm = sum(float(jnp.sum(jnp.abs(e.astype(jnp.float32))))
                  for e in jax.tree.leaves(s2.ef))
    assert ef_norm > 0  # dropped 90% of delta went into the EF buffer


def test_local_mesh_train_step():
    """Same step under a (1,1) mesh exercises shard_map/spec code paths."""
    mesh = make_local_mesh()
    cfg, params, batch = _smoke_setup()
    dcfg = D.DistConfig()
    with jax.set_mesh(mesh):
        state = D.init_state(params, dcfg, mesh)
        step = D.make_train_step(cfg, dcfg, mesh)
        state2, m = jax.jit(step)(state, batch)
    assert np.isfinite(float(m["loss"]))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    import repro.configs as configs
    from repro.fl import distributed as D
    from repro.models import model as M

    # Auto axes, as every production mesh in launch/mesh.py is built
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = dataclasses.replace(configs.get("qwen1p5_4b").smoke(),
                              local_iters=1, d_model=64, n_heads=2,
                              n_kv_heads=2, d_head=32, vocab=128)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    dcfg = D.DistConfig(theta_d=0.3, theta_u=0.4)
    with jax.set_mesh(mesh):
        state = D.init_state(params, dcfg, mesh)
        step = D.make_train_step(cfg, dcfg, mesh)
        state2, m = jax.jit(step)(state, batch)
        loss = float(m["loss"])
    assert jnp.isfinite(loss), loss
    # per-pod prev params must differ across pods after one round? They see
    # different batch halves, so the pods' local models diverge:
    prev = state2.prev_params["lm_head"]
    import numpy as np
    assert prev.shape[0] == 2
    assert not np.allclose(np.asarray(prev[0], np.float32),
                           np.asarray(prev[1], np.float32))
    print("MULTIPOD_OK", loss)
""")


@pytest.mark.slow
def test_multipod_execution_subprocess():
    """Real 2-pod execution (8 host devices): pods act as distinct clients.
    The child is pinned to the CPU backend: with a TPU runtime installed it
    would otherwise reach for the accelerator instead of the 8 forced host
    devices."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": os.path.expanduser("~"),
                            "JAX_PLATFORMS": "cpu"})
    assert "MULTIPOD_OK" in r.stdout, r.stdout + r.stderr


def test_error_feedback_sees_wire_format_quantization():
    """With compressed_collective, EF must accumulate the bf16 quantization
    error: at θ_u=0 nothing is sparsified away, so any EF mass can only be
    the wire-cast residual (the pre-fix code computed the residual before
    the cast and left EF exactly zero here)."""
    cfg, params, batch = _smoke_setup(tau=1)
    dcfg = D.DistConfig(theta_d=0.0, theta_u=0.0, local_lr=1e-2,
                        use_error_feedback=True, compressed_collective=True)
    state = D.init_state(params, dcfg, mesh=None)
    step = jax.jit(D.make_train_step(cfg, dcfg, mesh=None))
    s2, _ = step(state, batch)
    ef_norm = sum(float(jnp.sum(jnp.abs(e.astype(jnp.float32))))
                  for e in jax.tree.leaves(s2.ef))
    assert ef_norm > 0


def test_upload_compress_wire_dtype_residual():
    """tree_upload_compress returns the wire-format delta and an EF residual
    computed against it: wire + ef must reconstruct the corrected delta."""
    x = jax.random.normal(jax.random.PRNGKey(0), (512,)) * 0.3
    ef0 = jnp.zeros_like(x)
    wire, ef = D.tree_upload_compress({"w": x}, {"w": ef0},
                                      jnp.float32(0.0), "jnp",
                                      wire_dtype=jnp.bfloat16)
    assert wire["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(wire["w"].astype(jnp.float32) + ef["w"]),
        np.asarray(x), rtol=0, atol=1e-6)
    assert float(jnp.sum(jnp.abs(ef["w"]))) > 0   # bf16 rounding captured


def test_prev_int8_state_roundtrip():
    """int8 stale-buffer variant (beyond-paper #2c) trains and converges."""
    cfg, params, batch = _smoke_setup(tau=2)
    dcfg = D.DistConfig(theta_d=0.4, theta_u=0.4, local_lr=3e-2,
                        prev_int8=True)
    state = D.init_state(params, dcfg, mesh=None)
    # prev stored quantized
    leaf = jax.tree.leaves(state.prev_params)[0]
    assert leaf.dtype == jnp.int8 or leaf.dtype == jnp.float32  # q or scale
    step = jax.jit(D.make_train_step(cfg, dcfg, mesh=None))
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_dequantize_inverts_quantize_within_tolerance():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 2.0
    q = D.quantize_tree({"w": x})
    back = D.dequantize_tree(q, {"w": x})["w"]
    # absmax int8: error bounded by scale/2
    scale = float(jnp.max(jnp.abs(x))) / 127
    assert float(jnp.max(jnp.abs(back - x))) <= scale * 0.51 + 1e-6


def test_dp_only_policy_specs():
    """dp_only drops the model axis from every param spec."""
    import dataclasses
    from repro.launch.mesh import make_local_mesh
    cfg = dataclasses.replace(configs.get("mamba2_780m").smoke(),
                              dp_only=True)
    mesh = make_local_mesh()
    specs = M.param_specs(cfg, mesh)
    for s in jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index")):
        assert "model" not in [a for a in s if a is not None]
