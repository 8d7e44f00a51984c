#!/usr/bin/env python3
"""Run the Caesar round on a TPU through the system's own entry points.

    python3 chip_smoke.py               # one chip: phases a-d
    python3 chip_smoke.py --four-chips  # four chips: the sharded round only

One process drives the chip; every phase checks its results and any failure
ends the run with a non-zero exit code:

  a. print the devices JAX reports; stop unless they are TPUs;
  b. run the three Pallas kernels of the round at the full ResNet-18 width
     (11,164,362 parameters), vmapped over two participants as the round
     engine calls them, against their ``kernels/ref.py`` twins on the same
     chip: histogram counts, kept values, sign planes, counts, maxima and
     recovered vectors must be equal;
  c. run three Caesar rounds of ResNet-18 on CIFAR-10-shaped data through
     ``Simulator.run()``, with the backend that "auto" resolves to on a TPU
     ("pallas");
  d. run round 1 again with ``backend="pallas"`` and with ``backend="jnp"``
     and compare the global models and the traffic.

``--four-chips`` runs only the sharded round (``sharded=True`` over a
4-device "data" mesh) and compares it with the same seed unsharded on one
chip. Timings are printed as information, not as measurements. The last line
of stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.caesar import CaesarConfig  # noqa: E402
from repro.fl.driver import stratified_cohort  # noqa: E402
from repro.fl.simulation import SimConfig, Simulator  # noqa: E402
from repro.kernels import hybrid_compress as HC  # noqa: E402
from repro.kernels import recover as RC  # noqa: E402
from repro.kernels import ref as KREF  # noqa: E402
from repro.kernels import topk_threshold as TT  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import paper_models as PM  # noqa: E402

# compress's Σ|x| over the compressed set: both sides add the same float32
# terms, the kernel per (block row, lane) and then across blocks, XLA in its
# own tree. For non-negative terms the relative error of either order is at
# most (reduction depth)·2^-24, ≤ 512·6e-8 ≈ 3e-5 for a 512-row block.
SUM_RTOL = 1e-4
# round-1 parity, pallas vs jnp. Download compress/recover and the global
# histogram are bit-identical (phase b); the upload threshold is the same
# lower bin edge computed two ways (histogram cdf vs 8-step bisection), and
# training is the same XLA math in two different programs. An element whose
# magnitude sits within float rounding of a bin edge or of the threshold
# may fall on either side, moving that one element of one participant's
# upload. 1e-3 of the round's update norm admits a handful of such
# elements at ResNet-18 width (one is ~1/2600 of it) and fails on any
# systematic disagreement, such as a whole bin of a participant shifting.
UPDATE_RTOL = 1e-3
# cumulative traffic: the tolerance tests/test_round_engine.py holds
# same-seed trajectories to
TRAFFIC_RTOL = 1e-6
# four-chip parity: the accuracy tolerance of the sharded same-seed test
# (tests/test_round_engine.py, test_sharded_multidevice_subprocess)
ACC_TOL = 5e-3
FOUR_CHIP_ROUNDS = 2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def smoke_config(**overrides) -> SimConfig:
    """The paper's CIFAR-10 pairing at full width: ResNet-18, 100 clients,
    10% participation, τ=2 local iterations, default ragged pipelined
    in-process engine."""
    base = dict(dataset="cifar10", model="resnet18", n_clients=100,
                participation=0.1, rounds=3, data_scale=0.2, seed=0,
                caesar=CaesarConfig(tau=2))
    base.update(overrides)
    return SimConfig(**base)


def resnet18_width() -> int:
    shapes = jax.eval_shape(lambda k: PM.resnet18_init(k, n_classes=10),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


def _equal(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    n_bad = int(np.sum(got != want))
    check(got.shape == want.shape and n_bad == 0,
          f"{name}: {n_bad} of {want.size} elements differ from the ref twin")


def phase_kernels(n: int, interpret: bool, participants: int = 2,
                  ratio: float = 0.4, seed: int = 0) -> None:
    """Phase b: every kernel against its ref twin on seeded vectors."""
    kx, kl = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (participants, n), jnp.float32) * 0.05
    local = x + 0.01 * jax.random.normal(kl, (participants, n), jnp.float32)
    max_abs = jnp.max(jnp.abs(x), axis=1)

    def run(fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(jax.vmap(fn))(*args))
        return out, time.perf_counter() - t0

    hist_k, s = run(lambda v, m: TT.magnitude_histogram(
        v, m, interpret=interpret), x, max_abs)
    hist_r, _ = run(lambda v, m: KREF.magnitude_histogram(v, TT.N_BINS, m),
                    x, max_abs)
    _equal("magnitude_histogram", hist_k, hist_r)
    check(int(np.asarray(hist_k).sum()) == participants * n,
          "magnitude_histogram lost elements")
    print(f"phase b: magnitude_histogram equal ({participants} x {n}; "
          f"info: first call {s:.2f} s)")

    thr = jax.vmap(KREF.threshold_from_histogram, (0, 0, None))(
        hist_r, max_abs, jnp.float32(ratio))
    comp_k, s = run(lambda v, t: HC.hybrid_compress(
        v, t, interpret=interpret), x, thr)
    comp_r, _ = run(KREF.hybrid_compress, x, thr)
    for name, got, want in zip(("kept", "sign", "count", "max_abs"),
                               (comp_k[0], comp_k[1], comp_k[2], comp_k[4]),
                               (comp_r[0], comp_r[1], comp_r[2], comp_r[4])):
        _equal(f"hybrid_compress {name}", got, want)
    frac = float(np.mean(np.asarray(comp_r[2]))) / n
    check(0.3 < frac < 0.5, f"hybrid_compress compressed {frac:.3f} of the "
          f"vector at ratio {ratio}")
    sum_k, sum_r = np.asarray(comp_k[3]), np.asarray(comp_r[3])
    sum_err = float(np.max(np.abs(sum_k - sum_r) / sum_r))
    check(sum_err <= SUM_RTOL, f"hybrid_compress sum_abs off by {sum_err}")
    print(f"phase b: hybrid_compress kept/sign/count/max equal, sum_abs "
          f"rel diff {sum_err:.3e} (compressed {frac:.4f}; info: first call "
          f"{s:.2f} s)")

    kept, sign, count, sum_abs, smax = comp_r
    mean = sum_abs / jnp.maximum(count, 1)
    rec_k, s = run(lambda k, g, l, a, m: RC.recover(
        k, g, l, a, m, interpret=interpret), kept, sign, local, mean, smax)
    rec_r, _ = run(KREF.recover, kept, sign, local, mean, smax)
    _equal("recover", rec_k, rec_r)
    print(f"phase b: recover equal (info: first call {s:.2f} s)")


def _run_sim(sim: Simulator, label: str):
    t0 = time.perf_counter()
    hist = sim.run(log=lambda line: print(f"  {label}: {line}"))
    wall = time.perf_counter() - t0
    g = np.asarray(sim.global_flat)
    check(bool(np.isfinite(g).all()), f"{label}: non-finite global model")
    check(all(np.isfinite(a) and 0.0 <= a <= 1.0 for a in hist.accuracy),
          f"{label}: accuracy {hist.accuracy}")
    check(bool(hist.traffic_bits) and np.isfinite(hist.traffic_bits[-1])
          and hist.traffic_bits[-1] > 0, f"{label}: traffic "
          f"{hist.traffic_bits}")
    warm = hist.wall_per_round[1:]
    print(f"  {label}: info: run {wall:.1f} s, round 1 (with compiles) "
          f"{hist.wall_per_round[0]:.1f} s"
          + (f", later rounds median {statistics.median(warm):.3f} s"
             if warm else ""))
    return hist, g


def phase_rounds(cfg: SimConfig, want_backend: str) -> None:
    """Phase c: Caesar rounds through Simulator.run()."""
    sim = Simulator(cfg)
    check(sim.backend == want_backend,
          f"backend {cfg.backend!r} resolved to {sim.backend!r}, "
          f"not {want_backend!r}")
    check(sim.n_params == resnet18_width() or cfg.model != "resnet18",
          f"model has {sim.n_params} parameters")
    hist, _ = _run_sim(sim, f"{cfg.model}/{sim.backend}")
    print(f"phase c: {cfg.rounds} rounds of {cfg.model} on {cfg.dataset} "
          f"({sim.n_params} parameters, {sim.n_part} participants/round, "
          f"backend {sim.backend}): accuracy {hist.accuracy[-1]:.4f}, "
          f"traffic {hist.traffic_bits[-1]:.6e} bits")


def phase_parity(cfg: SimConfig, backend: str) -> None:
    """Phase d: round 1 with the kernels against round 1 with jnp."""
    one = dataclasses.replace(cfg, rounds=1, eval_every=1)
    out = {}
    for b in (backend, "jnp"):
        sim = Simulator(dataclasses.replace(one, backend=b))
        check(sim.backend == b, f"backend {b!r} resolved to {sim.backend!r}")
        hist, g = _run_sim(sim, f"round 1/{b}")
        out[b] = (hist.traffic_bits[-1], g, np.asarray(sim.flat0))
        del sim
    (bits_k, g_k, _), (bits_j, g_j, g0) = out[backend], out["jnp"]
    update = np.linalg.norm(g_j - g0)
    rel = float(np.linalg.norm(g_k - g_j) / update)
    n_diff = int(np.count_nonzero(g_k != g_j))
    bits_rel = abs(bits_k - bits_j) / bits_j
    print(f"phase d: round-1 global {backend} vs jnp: |diff|/|update| "
          f"{rel:.3e} (limit {UPDATE_RTOL}), {n_diff} of {g_j.size} "
          f"elements differ, max |diff| {float(np.max(np.abs(g_k - g_j))):.3e}"
          f"; traffic {bits_k:.6e} vs {bits_j:.6e} bits (rel diff "
          f"{bits_rel:.3e}, limit {TRAFFIC_RTOL})")
    check(rel <= UPDATE_RTOL, f"round-1 global: {backend} and jnp differ by "
          f"{rel:.3e} of the update")
    check(bits_rel <= TRAFFIC_RTOL, f"round-1 traffic: {bits_k} vs {bits_j}")


class UnshardedReference(Simulator):
    """The unsharded engine drawing the sharded engine's per-shard cohorts,
    so that both runs train the same clients on the same seed."""

    def __init__(self, cfg: SimConfig, n_shards: int):
        super().__init__(cfg)
        self.ref_shards = n_shards

    def _select_participants(self, rng, t):
        n = self.cfg.n_clients
        return stratified_cohort(rng, n, self.n_part, self.ref_shards), n, 0


def phase_four_chips(cfg: SimConfig, n_dev: int) -> None:
    """The sharded round over every device against the unsharded round."""
    sharded = Simulator(dataclasses.replace(cfg, sharded=True))
    check(sharded.n_dev == n_dev,
          f"the 'data' mesh has {sharded.n_dev} devices, not {n_dev}")
    h_sh, g_sh = _run_sim(sharded, f"sharded over {n_dev}")
    pool = sharded.store.pool
    homes = [d for shard in pool.addressable_shards
             for d in shard.data.devices()]
    rows = sorted({shard.data.shape[0] for shard in pool.addressable_shards})
    print(f"four chips: pool {pool.shape} rows on devices "
          f"{sorted(d.id for d in homes)}, {rows} rows per shard")
    check(len(set(homes)) == n_dev and rows == [pool.shape[0] // n_dev],
          f"pool rows sit on {len(set(homes))} devices, not {n_dev}")
    del sharded

    ref = UnshardedReference(cfg, n_dev)
    h_ref, g_ref = _run_sim(ref, "unsharded on one chip")
    ref_homes = {d for shard in ref.store.pool.addressable_shards
                 for d in shard.data.devices()}
    check(len(ref_homes) == 1, f"unsharded pool on {len(ref_homes)} devices")

    acc = max(abs(a - b) for a, b in zip(h_sh.accuracy, h_ref.accuracy))
    bits = abs(h_sh.traffic_bits[-1] - h_ref.traffic_bits[-1]) \
        / h_ref.traffic_bits[-1]
    rel = float(np.linalg.norm(g_sh - g_ref)
                / np.linalg.norm(g_ref - np.asarray(ref.flat0)))
    print(f"four chips: sharded vs unsharded after {cfg.rounds} rounds: "
          f"max |acc diff| {acc:.4f} (limit {ACC_TOL}), traffic rel diff "
          f"{bits:.3e} (limit {TRAFFIC_RTOL}), global |diff|/|update| "
          f"{rel:.3e} (reported)")
    check(acc <= ACC_TOL, f"accuracy {h_sh.accuracy} vs {h_ref.accuracy}")
    check(bits <= TRAFFIC_RTOL,
          f"traffic {h_sh.traffic_bits} vs {h_ref.traffic_bits}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the Caesar round on a TPU and check its results.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round over four chips, "
                         "against the unsharded round on one")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev['platform']}); "
              "nothing was run", file=sys.stderr)
        return 1
    print(f"phase a: platform={dev['platform']} "
          f"device_kind={dev['kind']} devices={dev['count']}")
    if args.four_chips:
        check(dev["count"] == 4, f"--four-chips needs 4 devices, "
              f"found {dev['count']}")
        # 12 participants: 3 per shard
        phase_four_chips(smoke_config(participation=0.12,
                                      rounds=FOUR_CHIP_ROUNDS), 4)
    else:
        phase_kernels(resnet18_width(), interpret=False)
        cfg = smoke_config()
        phase_rounds(cfg, "pallas")
        phase_parity(cfg, "pallas")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"info: device 0 peak memory "
              f"{stats['peak_bytes_in_use'] / 2 ** 30:.2f} GiB")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
