"""Compile the main-path kernels for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present. These tests catch what the
Pallas interpreter cannot — Mosaic's block-tiling rules, VMEM limits, and
a round step that would not fit the chip's HBM — at the full ResNet-18 width
the Track-A round runs at, and under ``jax.vmap`` over a chunk of
participants the way the round engine calls the kernels.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and every test worker imports
this file. JAX's persistent compilation cache stays off around the
compiles (an entry compiled for a described chip cannot be read back here).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compression as C
from repro.kernels import hybrid_compress as HC
from repro.kernels import recover as RC
from repro.kernels import topk_threshold as TT
from repro.models import paper_models as PM

RESNET18_PARAMS = 11_164_362
CHUNK = 8                        # participants per vmapped chunk step
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_resnet18_width():
    shapes = jax.eval_shape(lambda k: PM.resnet18_init(k, n_classes=10),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes)) == RESNET18_PARAMS


def _kernel_case(name):
    n, b = RESNET18_PARAMS, CHUNK
    vec, scal = (b, n), (b,)
    if name == "magnitude_histogram":
        fn = lambda x, m: TT.magnitude_histogram(x, m, interpret=False)
        return fn, [(vec, jnp.float32), (scal, jnp.float32)]
    if name == "hybrid_compress":
        fn = lambda x, t: HC.hybrid_compress(x, t, interpret=False)
        return fn, [(vec, jnp.float32), (scal, jnp.float32)]
    fn = lambda k, s, l, a, m: RC.recover(k, s, l, a, m, interpret=False)
    return fn, [(vec, jnp.float32), (vec, jnp.int8), (vec, jnp.float32),
                (scal, jnp.float32), (scal, jnp.float32)]


@pytest.mark.parametrize("name", ["magnitude_histogram", "hybrid_compress",
                                  "recover"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tier_chunk_step_fits_one_v5e(one_chip):
    """The round engine's jitted per-tier step, Pallas backend, ResNet-18:
    the three kernels lower inside it and one chunk fits the chip's HBM."""
    from repro.core.caesar import CaesarConfig
    from repro.fl.simulation import SimConfig, Simulator
    sim = Simulator(SimConfig(
        dataset="cifar10", model="resnet18", n_clients=100,
        participation=0.1, data_scale=0.01, backend="pallas",
        caesar=CaesarConfig(tau=2)))
    assert sim.n_params == RESNET18_PARAMS
    n, cap, c, tau, b = sim.n_params, 32, CHUNK, 2, 1

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = sim.executor._tier_chunk_defer.lower(
        s((cap, n)), s((cap, 0)), s((n,)), s((C.N_BINS,)), s(()),
        s((c,), jnp.int32), s((c,)), s((c, tau, b, 32, 32, 3)),
        s((c, tau, b), jnp.int32), s((c, tau, b)), s((c, tau)), s(()),
        s((c,)), s((c,)), s((), jnp.uint32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < V5E_HBM_BYTES, live


def test_sharded_round_compiles_for_v5e_2x2(topo):
    """The sharded engine over a 4-chip "data" mesh, Pallas backend: the
    per-round global histogram and the shard-mapped tier-chunk step both
    hold their kernels inside shard_map (a Mosaic kernel cannot be
    partitioned automatically)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.caesar import CaesarConfig
    from repro.fl.executor import RoundExecutor
    from repro.fl.simulation import SimConfig
    mesh = Mesh(np.array(topo.devices), ("data",))
    d = mesh.shape["data"]
    cfg = SimConfig(dataset="cifar10", model="resnet18", n_clients=100,
                    participation=0.12, backend="pallas", sharded=True,
                    caesar=CaesarConfig(tau=2))
    init_fn, apply_fn = PM.MODELS["resnet18"]
    shapes = jax.eval_shape(lambda k: init_fn(k, n_classes=10),
                            jax.random.PRNGKey(0))
    _, spec = C.flatten_tree(jax.tree.map(
        lambda a: np.zeros(a.shape, a.dtype), shapes))
    ex = RoundExecutor(cfg, apply_fn, spec, "pallas", quantize=False,
                       n_part=12, mesh=mesh)
    n, cap, c, tau, b = spec.n_params, 32, 2, 2, 1

    def s(shape, pspec, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, pspec))

    rows, rep = P("data", None), P()
    hist = ex._hist.lower(s((n,), rep)).compile()
    assert "tpu_custom_call" in hist.as_text()
    step = ex._tier_chunk.lower(
        s((cap, n), rows), s((cap, 0), rows), s((d, n), rows),
        s((n,), rep), s((C.N_BINS,), rep), s((), rep),
        s((d * c,), P("data"), jnp.int32), s((d * c,), P("data")),
        s((d * c, tau, b, 32, 32, 3), P("data")),
        s((d * c, tau, b), P("data"), jnp.int32),
        s((d * c, tau, b), P("data")), s((d * c, tau), P("data")),
        s((), rep), s((d * c,), P("data")), s((d * c,), P("data")),
        s((), rep, jnp.uint32)).compile()
    assert step.as_text().count("tpu_custom_call") >= 3
