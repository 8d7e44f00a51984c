"""Round-engine benchmark: seed pytree/quantile path vs fused flat engine.

Measures, on a CPU-budget 100-client/20-round HAR config:
  * per-round wall-clock of the seed ``participant_round`` path (preserved
    verbatim below: per-participant pytree flatten/unflatten ×4, exact-
    quantile thresholds, per-leaf host-side gather/aggregate/scatter) vs the
    fused flat-parameter engine (DESIGN.md §1),
  * threshold-selection time (exact quantile vs jnp histogram vs Pallas
    interpret histogram) on an [n_params] vector,
  * the fused engine with and without the double-buffered sampling pipeline
    (SimConfig.pipelined) — the overlap speedup plus same-seed parity,
  * end-to-end simulation wall and final accuracy for BOTH engines with the
    same seeds (trajectory-parity evidence).

Per-round medians exclude round 1 (the jit compile, reported separately as
``round1_s``, ``History.wall_per_round[0]``) via the warmup drop in
`_median_steady`.

The default uses τ=1 local steps so the measurement isolates the round
*engine* (the local-SGD math is line-for-line identical in both engines and
would otherwise dominate the ratio); a τ=5 training-heavy config is recorded
alongside.

The **ragged-vs-masked** section (DESIGN.md §8) measures the plan-shaped
tier engine against the uniform-cap masked engine at τ=5 — the
training-bound regime where masked padding wastes the most FLOPs — on the
heterogeneous capability draw (participant-scoped Eq. 8–9 planning, the
production default), at the 100-client HAR point and the dense
1000-client/P=500 point, with same-seed trajectory parity and tier
occupancy / jit-cache telemetry. Emits BENCH_round.json at the repo root
and under experiments/bench/.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import caesar as CA
from repro.core import compression as C
from repro.core.caesar import CaesarConfig
from repro.fl.simulation import SimConfig, Simulator
from repro.kernels import topk_threshold as TT

ROOT = Path(__file__).resolve().parent.parent


def bench_config(tau: int, n_clients: int, rounds: int,
                 pipelined: bool = True) -> SimConfig:
    # plan_scope="all" pins the PLANNING layer to what LegacyEngine below
    # computes (plan_round without a participant mask), so the seed-vs-fused
    # comparison isolates the execution engine — not the PR-2 planner fix.
    # ragged=False: the legacy engine runs at the [τ, b_max] cap, so the
    # masked engine is its like-for-like counterpart; the ragged engine is
    # measured separately (bench_ragged) against the masked one.
    return SimConfig(dataset="har", scheme="caesar", n_clients=n_clients,
                     participation=0.1, rounds=rounds, data_scale=0.25,
                     eval_every=10 ** 6,   # final-round eval only
                     pipelined=pipelined, ragged=False,
                     caesar=CaesarConfig(tau=tau, b_max=16,
                                         plan_scope="all"))


# ---------------------------------------------------------------------------
# The seed round engine, preserved for comparison. This is the pre-refactor
# fl/simulation.py hot path: every participant re-flattens/unflattens the
# model pytree four times per round and every threshold is a full
# jnp.quantile; gather, aggregation and the local-model scatter run per leaf
# on the host between separate dispatches.
# ---------------------------------------------------------------------------

class LegacyEngine:
    def __init__(self, cfg: SimConfig):
        self.cfg = cfg
        self.sim = Simulator(cfg)          # reuse data/partition/capability
        self.caesar_state = CA.init_state(
            jnp.asarray(self.sim.volumes, jnp.float32),
            jnp.asarray(self.sim.label_dist), cfg.caesar)
        self._build_jits()

    def _build_jits(self):
        apply_fn = self.sim.apply_fn

        def ce_loss(params, x, y, w):
            logits = apply_fn(params, x)
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
            return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)

        def local_train(params, xs, ys, ws, iter_mask, lr):
            def step(p, inp):
                x, y, w, m = inp
                g = jax.grad(ce_loss)(p, x, y, w)
                newp = jax.tree.map(lambda a, b_: a - lr * m * b_, p, g)
                return newp, None
            out, _ = jax.lax.scan(step, params, (xs, ys, ws, iter_mask))
            return out

        def participant_round(global_p, local_p, xs, ys, ws, iter_mask, lr,
                              theta_d, theta_u, use_recovery, quantize):
            flat_g, treedef, leaves = C._flatten(global_p)
            flat_l, _, _ = C._flatten(local_p)
            comp = C.hybrid_compress(flat_g, theta_d)
            recovered = jax.lax.cond(
                use_recovery,
                lambda: C.hybrid_recover(comp, flat_l),
                lambda: jnp.where(comp.mask, flat_l, comp.kept))
            down_bits = comp.payload_bits()
            w_init = C._unflatten(recovered, treedef, leaves)
            w_fin = local_train(w_init, xs, ys, ws, iter_mask, lr)
            flat_i, _, _ = C._flatten(w_init)
            flat_f, _, _ = C._flatten(w_fin)
            delta = flat_i - flat_f
            gnorm = jnp.linalg.norm(delta)

            def topk():
                sp, bits = C.topk_sparsify(delta, theta_u)
                return sp, bits.astype(jnp.float32)

            def quant():
                cc = C.hybrid_compress(delta, theta_u)
                approx = jnp.where(cc.mask,
                                   cc.sign.astype(jnp.float32) * cc.mean_abs,
                                   cc.kept)
                return approx, cc.payload_bits().astype(jnp.float32)

            up, up_bits = jax.lax.cond(quantize, quant, topk)
            return (C._unflatten(up, treedef, leaves), w_fin, down_bits,
                    up_bits, gnorm)

        self._round_vmapped = jax.jit(jax.vmap(
            participant_round,
            in_axes=(None, 0, 0, 0, 0, 0, None, 0, 0, None, None)))

    def run(self, rounds: int | None = None):
        """The seed driver loop. Returns (per-round wall list, final tree)."""
        cfg = self.cfg
        sim = self.sim
        ccfg = cfg.caesar
        n, b_max, tau = cfg.n_clients, ccfg.b_max, ccfg.tau
        n_part = sim.n_part
        global_p = sim.params0
        local_p = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), sim.params0)
        walls = []
        for t in range(1, (rounds or cfg.rounds) + 1):
            w0 = time.perf_counter()
            # same per-round SeedSequence streams as the fused engine, so
            # both engines train on identical participants and batches
            parts, xs, ys = sim._prefetch_round(t)
            mu, bw_d, bw_u = sim.cap.snapshot(t)
            from repro.optim import sgd as SGD
            # keep lr a device scalar: float() here blocked on the (tiny)
            # decay computation every round, a sync the timed loop never
            # needed — the jitted step traces the scalar like any operand
            lr = SGD.lr_at(cfg.sgd, jnp.float32(t - 1))
            plan = CA.plan_round(self.caesar_state, jnp.int32(t), ccfg,
                                 jnp.asarray(bw_d, jnp.float32),
                                 jnp.asarray(bw_u, jnp.float32),
                                 jnp.asarray(mu, jnp.float32),
                                 float(sim.model_bits))
            # per-round plan syncs preserved verbatim: this loop IS the
            # measured legacy baseline the fused engine is compared against
            theta_d = np.asarray(plan.theta_d)[parts]  # repro: noqa=REP006
            theta_u = np.asarray(plan.theta_u)[parts]  # repro: noqa=REP006
            batch = np.asarray(plan.batch)[parts]  # repro: noqa=REP006
            taus = np.full(n_part, tau)
            ws, ims = sim._batch_masks(batch, taus, b_max, tau)
            lp_sel = jax.tree.map(lambda a: a[parts], local_p)
            ups, new_lp, down_bits, up_bits, gnorms = self._round_vmapped(
                global_p, lp_sel, xs, ys, ws, ims, lr,
                jnp.asarray(theta_d, jnp.float32),
                jnp.asarray(theta_u, jnp.float32),
                True, False)
            agg = jax.tree.map(lambda u: jnp.mean(u, axis=0), ups)
            global_p = jax.tree.map(lambda g, a: g - a, global_p, agg)
            local_p = jax.tree.map(
                lambda all_, new: all_.at[parts].set(new), local_p, new_lp)
            mask = np.zeros(n, bool); mask[parts] = True
            self.caesar_state = CA.post_round(
                self.caesar_state, jnp.asarray(mask), jnp.int32(t))
            # deliberate sync, as the seed path did: the walls measure a
            # completed round
            np.asarray(down_bits); np.asarray(up_bits)  # repro: noqa=REP006
            walls.append(time.perf_counter() - w0)
        return walls, global_p

    def final_accuracy(self, tree, n_eval=1000) -> float:
        sim = self.sim
        ne = min(n_eval, len(sim.data.y_test))
        flat = C.flatten_vector(tree, sim.spec)
        return float(sim._eval(flat, jnp.asarray(sim.data.x_test[:ne]),
                               jnp.asarray(sim.data.y_test[:ne])))


# ---------------------------------------------------------------------------

def _median_steady(walls, warmup=1):
    body = walls[warmup:] if len(walls) > warmup else walls
    return statistics.median(body)


def bench_threshold(n_params: int, reps: int) -> dict:
    """Threshold-selection microbench on a model-sized vector."""
    x = jax.random.normal(jax.random.PRNGKey(0), (n_params,)) * 3.0
    ratio = jnp.float32(0.35)
    cands = {
        "quantile": jax.jit(lambda v, r: C.magnitude_threshold(v, r)),
        "hist_jnp": jax.jit(lambda v, r: C.fused_threshold(v, r, "jnp")),
        "hist_pallas_interp": jax.jit(
            lambda v, r: TT.threshold(v, r, interpret=True)),
    }
    out = {}
    for name, fn in cands.items():
        fn(x, ratio).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(x, ratio).block_until_ready()
        out[f"{name}_ms"] = (time.perf_counter() - t0) / reps * 1e3
    # all agree within one bin width
    q = float(cands["quantile"](x, ratio))
    h = float(cands["hist_jnp"](x, ratio))
    out["bin_width"] = float(jnp.max(jnp.abs(x))) / 256.0
    out["quantile_minus_hist"] = q - h
    return out


def bench_engines(tau: int, n_clients: int, rounds: int) -> dict:
    cfg = bench_config(tau, n_clients, rounds)
    # e2e clocks cover the run phase only, for both engines symmetrically
    # (construction — dataset synthesis, partitioning, jit builds — is
    # one-time and identical-by-construction between them)
    sim = Simulator(cfg)
    t0 = time.perf_counter()
    h = sim.run()         # raw per-round walls land in History.wall_per_round
    fused_e2e = time.perf_counter() - t0
    # same engine without the sampling/step overlap, to isolate the
    # double-buffered pipeline's contribution (same-seed identical output)
    h_sync = Simulator(bench_config(tau, n_clients, rounds,
                                    pipelined=False)).run()
    leg = LegacyEngine(cfg)          # seed engine on identical data/seeds
    t0 = time.perf_counter()
    walls, tree = leg.run()
    seed_e2e = time.perf_counter() - t0
    seed_acc = leg.final_accuracy(tree, cfg.eval_samples)
    # wall_per_round samples are captured before the eval block, so both
    # engines' medians run over the same per-round population
    seed_ms = _median_steady(walls) * 1e3
    fused_ms = _median_steady(h.wall_per_round) * 1e3
    sync_ms = _median_steady(h_sync.wall_per_round) * 1e3
    return {
        "tau": tau, "n_clients": n_clients, "rounds": rounds,
        "n_params": sim.n_params, "backend": sim.backend,
        "chunk": sim.executor.chunk,
        "seed_round_ms": seed_ms,
        "fused_round_ms": fused_ms,
        "sync_round_ms": sync_ms,
        "speedup": seed_ms / fused_ms,
        "pipeline_speedup": sync_ms / fused_ms,
        "seed_e2e_s": seed_e2e,
        "fused_e2e_s": fused_e2e,
        "round1_s": h.wall_per_round[0],
        "seed_final_acc": seed_acc,
        "fused_final_acc": h.accuracy[-1] if h.accuracy else float("nan"),
        "pipelined_equals_sync": h.accuracy == h_sync.accuracy,
    }


def bench_ragged(tau: int, n_clients: int, rounds: int,
                 participation: float = 0.1,
                 data_scale: float = 0.25) -> dict:
    """Plan-shaped ragged engine vs the uniform-cap masked engine, same
    seed, on the heterogeneous capability draw (participant-scoped Eq. 8–9
    planning — the production default, NOT the legacy plan_scope="all" of
    `bench_config`: the ragged win is a property of the plan's b-spread)."""
    def cfg(ragged):
        return SimConfig(dataset="har", scheme="caesar",
                         n_clients=n_clients, participation=participation,
                         rounds=rounds, data_scale=data_scale,
                         eval_every=10 ** 6, ragged=ragged,
                         caesar=CaesarConfig(tau=tau, b_max=16))

    # cold run (trajectory + lazy tier-shape compiles), then a same-seed
    # replay against the warm jit caches: the ragged engine compiles each
    # tier shape the first round that occupies it, so cold mid-run walls
    # fold compiles in — the warm replay is the steady state. The masked
    # engine gets the identical protocol (its single compile already falls
    # in the dropped round 1, so warm ≈ cold there).
    sim_r = Simulator(cfg(True))
    t0 = time.perf_counter()
    h_r = sim_r.run()
    ragged_cold_e2e = time.perf_counter() - t0
    tel = sim_r.executor.telemetry()
    sim_r.reset()
    t0 = time.perf_counter()
    h_rw = sim_r.run()
    ragged_e2e = time.perf_counter() - t0
    assert h_rw.accuracy == h_r.accuracy     # replay really is same-seed
    sim_m = Simulator(cfg(False))
    h_m = sim_m.run()
    sim_m.reset()
    t0 = time.perf_counter()
    h_mw = sim_m.run()
    masked_e2e = time.perf_counter() - t0
    ragged_ms = _median_steady(h_rw.wall_per_round) * 1e3
    masked_ms = _median_steady(h_mw.wall_per_round) * 1e3
    return {
        "tau": tau, "n_clients": n_clients,
        "participants": sim_r.n_part, "rounds": rounds,
        "n_params": sim_r.n_params, "chunk": sim_r.executor.chunk,
        "masked_round_ms": masked_ms,
        "ragged_round_ms": ragged_ms,
        "speedup": masked_ms / ragged_ms,
        "masked_e2e_s": masked_e2e,
        "ragged_e2e_s": ragged_e2e,
        "ragged_cold_e2e_s": ragged_cold_e2e,   # includes tier-shape compiles
        "round1_s": h_r.wall_per_round[0],
        "work_fraction": tel["work_fraction"],
        "tier_occupancy": tel["tier_occupancy"],
        "compiled_tier_shapes": tel["compiled_tier_shapes"],
        "shape_lattice_bound": tel["shape_lattice_bound"],
        # parity: same plan ⇒ identical simulated time; trajectories agree
        # to float-reduction noise (reduction order over the padded batch)
        "ragged_final_acc": h_r.accuracy[-1],
        "masked_final_acc": h_m.accuracy[-1],
        "acc_equal": h_r.accuracy == h_m.accuracy,
        "max_acc_diff": max(abs(a - b) for a, b in
                            zip(h_r.accuracy, h_m.accuracy)),
        "traffic_rel_diff": abs(h_r.traffic_bits[-1] - h_m.traffic_bits[-1])
        / max(h_m.traffic_bits[-1], 1e-12),
        "sim_time_equal": h_r.sim_time == h_m.sim_time,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI import/perf-path checking")
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=20)
    args = ap.parse_args()

    if args.smoke:
        clients, rounds, reps = 20, 3, 3
    else:
        clients, rounds, reps = args.clients, args.rounds, 30

    results = {"config": {"dataset": "har", "clients": clients,
                          "rounds": rounds, "smoke": args.smoke}}

    # csv rows follow the repo convention (benchmarks/common.py):
    # "name,us_per_call,derived" — the middle field is MICROSECONDS per
    # round; the human-readable derived column quotes milliseconds.
    primary = bench_engines(tau=1, n_clients=clients, rounds=rounds)
    results["round_engine"] = primary
    print(f"bench_round/engine_tau1,{primary['fused_round_ms'] * 1e3:.0f},"
          f"speedup={primary['speedup']:.2f}x "
          f"(seed {primary['seed_round_ms']:.0f}ms → fused "
          f"{primary['fused_round_ms']:.0f}ms)")
    print(f"bench_round/pipeline_tau1,{primary['fused_round_ms'] * 1e3:.0f},"
          f"overlap={primary['pipeline_speedup']:.2f}x "
          f"(sync {primary['sync_round_ms']:.0f}ms → pipelined "
          f"{primary['fused_round_ms']:.0f}ms; same-seed parity="
          f"{primary['pipelined_equals_sync']})")

    if not args.smoke:
        heavy = bench_engines(tau=5, n_clients=clients, rounds=rounds)
        results["round_engine_tau5"] = heavy
        print(f"bench_round/engine_tau5,{heavy['fused_round_ms'] * 1e3:.0f},"
              f"speedup={heavy['speedup']:.2f}x")

    # plan-shaped ragged vs uniform-cap masked (DESIGN.md §8): τ=5 is the
    # training-bound regime where the masked padding waste peaks
    rag = bench_ragged(tau=1 if args.smoke else 5, n_clients=clients,
                       rounds=rounds)
    results["ragged_tau5" if not args.smoke else "ragged_smoke"] = rag
    print(f"bench_round/ragged_tau{rag['tau']},"
          f"{rag['ragged_round_ms'] * 1e3:.0f},"
          f"speedup={rag['speedup']:.2f}x "
          f"(masked {rag['masked_round_ms']:.0f}ms → ragged "
          f"{rag['ragged_round_ms']:.0f}ms; work_fraction="
          f"{rag['work_fraction']:.2f}; max_acc_diff="
          f"{rag['max_acc_diff']:.1e}; shapes="
          f"{rag['compiled_tier_shapes']}/{rag['shape_lattice_bound']})")

    if not args.smoke:
        # the dense 1000-client/P=500 cohort: the compute-bound point where
        # the ROADMAP demands the hot path scale — fewer rounds (a dense
        # masked τ=5 round is ~1 min on the CPU container)
        dense = bench_ragged(tau=5, n_clients=1000, rounds=4,
                             participation=0.5, data_scale=1.0)
        results["ragged_dense_tau5"] = dense
        print(f"bench_round/ragged_dense_tau5,"
              f"{dense['ragged_round_ms'] * 1e3:.0f},"
              f"speedup={dense['speedup']:.2f}x "
              f"(masked {dense['masked_round_ms']:.0f}ms → ragged "
              f"{dense['ragged_round_ms']:.0f}ms; work_fraction="
              f"{dense['work_fraction']:.2f})")

    thr = bench_threshold(primary["n_params"], reps)
    results["threshold_selection"] = thr
    print(f"bench_round/threshold,{thr['hist_jnp_ms'] * 1e3:.0f},"
          f"quantile={thr['quantile_ms']:.1f}ms "
          f"hist_jnp={thr['hist_jnp_ms']:.1f}ms")

    payload = json.dumps(results, indent=1, default=float)
    # smoke runs (CI) must not clobber the recorded full-run numbers
    name = "BENCH_round_smoke.json" if args.smoke else "BENCH_round.json"
    (ROOT / name).write_text(payload)
    out2 = ROOT / "experiments" / "bench"
    out2.mkdir(parents=True, exist_ok=True)
    (out2 / name).write_text(payload)
    print(f"wrote {name}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
