"""Production mesh builders. Functions (never module-level constants) so that
importing this module never touches jax device state."""
from __future__ import annotations

import jax
import numpy as np


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi-pod adds the 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """1×1 mesh on the single local device (smoke tests / examples)."""
    return _mesh((1, 1), ("data", "model"))


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Initialize jax.distributed for a multi-host "data" mesh, idempotently.

    With no arguments, relies on jax's cluster auto-detection (SLURM, GKE,
    or the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID
    env triplet); failures there (no cluster, or jax already initialized —
    initialize() must precede the first jax computation in the process) fall
    back to single-process. With EXPLICIT arguments a failure is a
    misconfiguration and propagates. Returns True when the runtime is (or
    already was) multi-process — callers use this to decide between
    `jax.device_put` and process-local array assembly (`host_local_array`).
    Safe to call twice: a live distributed client is left untouched.
    """
    if jax.process_count() > 1:
        return True
    explicit = (coordinator_address is not None or num_processes is not None
                or process_id is not None)
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except (RuntimeError, ValueError):
        if explicit:
            raise
        # nothing to auto-detect, or jax already up: stay single-process
    return jax.process_count() > 1


def make_data_mesh(n_devices: int | None = None):
    """1-D ("data",) mesh over all addressable devices (every local device;
    after `init_distributed`, jax.devices() spans every host's devices, so
    the same call yields the multi-host mesh).

    The sharded Track-A round engine (fl/simulation.py, DESIGN.md §7) places
    the [n_clients, n_params] local buffer and the participant chunks across
    this axis.
    """
    n = n_devices or len(jax.devices())
    return _mesh((n,), ("data",))


def host_local_array(mesh, spec, arr):
    """Build a global array sharded by ``spec`` from host data.

    Single-process: a plain `jax.device_put` (the host holds every row).
    Multi-process: the round engine's host loop is same-seed deterministic,
    so every process computes the identical global value; each process
    materializes on device only the shards its own devices address (the
    callback slices ``arr`` per shard index), so remote rows are never
    transferred through this host — process-local buffer rows, DESIGN.md
    §7. Pass views (e.g. np.broadcast_to) to keep the host-side footprint
    of large broadcasts at zero.
    """
    sharding = jax.sharding.NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def fetch_global(arr):
    """np.ndarray of a possibly multi-host output.

    Fully-addressable arrays (single process, or replicated outputs) are a
    plain np.asarray; "data"-sharded outputs on a multi-host mesh need an
    allgather of the per-process shards first.
    """
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
