"""Paper Fig. 10 / §6.5: scalability across device counts.

Two studies live here:

* ``run()`` — the paper-figure reproduction (30/60/100 clients, CPU budget),
  unchanged CSV/JSON conventions.
* the **round-engine scale study** (``--scale`` / ``--smoke``) — 500/1000/
  2000-client cohorts through the pipelined + auto-chunked + plan-shaped
  ragged engine (DESIGN.md §7–8), emitting ``BENCH_scale.json`` with peak
  host memory, s/round, tier-occupancy / jit-cache / work-fraction
  telemetry per scale point plus same-seed trajectory parities
  (pipelined-vs-synchronous, auto-vs-explicit chunk, ragged-vs-masked);
  out-of-tolerance parity — or a ragged jit cache exceeding its static
  tier-lattice bound — fails the run, which is the CI gate. A bf16
  local-buffer twin of the 1000-client point records the storage/accuracy
  trade, and the full-cardinality speech point (85k×4000-sample clips, 35
  classes) rides the ragged engine. The **registered-scale study**
  (DESIGN.md §9) fixes a ~1k active cohort while registration grows
  10k → 100k → 1M: the participation-keyed `ClientStateStore` must keep
  peak RSS flat (the 100k-vs-10k ratio is a hard CI gate), and a
  dense-state twin (``state_capacity=0``) gates that slot indirection
  stays numerically invisible. Every point runs in a **fresh
  subprocess** so ``ru_maxrss`` (a process-lifetime high-water mark) is a
  clean per-point measurement; the sharded point forces a multi-device
  host platform via XLA_FLAGS.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

SCALES = [30, 60, 100]
SCHEMES = ["fedavg", "caesar"]


def run(dataset="har", log=lambda s: None):
    from benchmarks import common as CM
    out = {}
    for n in SCALES:
        for scheme in SCHEMES:
            cfg = CM.sim_config(dataset, scheme, n_clients=n,
                                participation=max(0.1, 6 / n))
            h, wall = CM.run_sim(cfg, log)
            out[f"{scheme}@n{n}"] = {
                "final_acc": h.accuracy[-1],
                "traffic_gb": h.traffic_bits[-1] / 8e9,
                "time_s": h.sim_time[-1]}
            CM.csv_row(f"fig10/{scheme}/n{n}",
                       wall / max(len(h.rounds), 1) * 1e6,
                       f"acc={h.accuracy[-1]:.3f};traffic_gb={h.traffic_bits[-1]/8e9:.3f};time_s={h.sim_time[-1]:.0f}")
    CM.save("fig10_scales", out)
    return out


# ---------------------------------------------------------------------------
# Round-engine scale study (BENCH_scale.json)
# ---------------------------------------------------------------------------

def run_point(n_clients: int, chunk_size, rounds: int,
              participation: float = 0.1, sharded: bool = False,
              seed: int = 0, data_scale: float = 1.0, tau: int = 2,
              pipelined: bool = True, dataset: str = "har",
              chunk_budget_mb: float = 1024.0,
              ragged: bool = True, buffer_dtype: str = "float32",
              state_capacity=None, state_offload: str = "none",
              measure_eviction_error: bool = False,
              compare_pipeline: bool = False, n_dev=None) -> dict:
    """One scale point, measured in THIS process (run it in a fresh
    subprocess for a clean ru_maxrss high-water mark). Evaluates EVERY
    round so the recorded accuracy list is a genuine trajectory (the
    parity checks compare all of it, not just the final point).
    ``chunk_size`` follows SimConfig: None ⇒ auto_chunk, 0 ⇒ one chunk.

    ``compare_pipeline=True`` additionally runs the SYNCHRONOUS driver
    AFTER the measured (pipelined) run in the same process — back-to-back
    medians on the same warm machine state resolve overlap gains that
    inter-subprocess noise would bury; running the measured point first
    keeps its peak_rss_mb clean (ru_maxrss is a process-lifetime high-water
    mark the second run could only inflate), and the second-run page-cache
    warmth favors the sync baseline, i.e. biases the reported speedup
    conservatively. Reports sync_s_per_round / pipeline_speedup /
    pipeline_parity (same-seed trajectory agreement between the two).

    ``n_dev`` is the device count a sharded point was specified for; a
    mesh of any other size fails the point instead of measuring it."""
    import gc

    from repro.core import compression as C
    from repro.core.caesar import CaesarConfig
    from repro.fl.simulation import SimConfig, Simulator

    def build(pipe):
        return SimConfig(dataset=dataset, scheme="caesar",
                         n_clients=n_clients, participation=participation,
                         rounds=rounds, data_scale=data_scale, eval_every=1,
                         seed=seed, caesar=CaesarConfig(tau=tau, b_max=16),
                         chunk_size=chunk_size,
                         chunk_budget_mb=chunk_budget_mb,
                         ragged=ragged, buffer_dtype=buffer_dtype,
                         state_capacity=state_capacity,
                         state_offload=state_offload,
                         measure_eviction_error=measure_eviction_error,
                         pipelined=pipe, sharded=sharded)

    def median_warm(h):
        walls = h.wall_per_round[1:] if len(h.wall_per_round) > 1 \
            else h.wall_per_round
        return statistics.median(walls)

    out = {}
    t0 = time.perf_counter()
    sim = Simulator(build(pipelined))
    if n_dev is not None and sim.n_dev != n_dev:
        raise RuntimeError(f"point specified for {n_dev} devices got a "
                           f"{sim.n_dev}-device mesh")
    h = sim.run()
    wall = time.perf_counter() - t0
    out.update({
        "dataset": dataset, "n_clients": n_clients,
        "participants": sim.n_part,
        "chunk_size": chunk_size, "chunk": sim.executor.chunk,
        "chunk_budget_mb": chunk_budget_mb,
        "chunk_workset_mb": sim.executor.chunk * C.ROUND_WORKSET_ARRAYS
        * 4 * sim.n_params / 2 ** 20,
        "pipelined": pipelined,
        "sharded": sharded, "n_dev": sim.n_dev,
        "ragged": ragged, "buffer_dtype": buffer_dtype,
        "rounds": rounds, "n_params": sim.n_params,
        "s_per_round": median_warm(h),
        "round1_s": h.wall_per_round[0],
        # plan-shaped execution telemetry (DESIGN.md §8): per-tier
        # participant counts, jit-cache size vs its lattice bound, and the
        # plan-shaped fraction of the masked engine's FLOPs
        **sim.executor.telemetry(),
        # ru_maxrss is KB on Linux
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        / 1024.0,
        # dense-equivalent O(n_clients) figure kept for continuity; the
        # store telemetry below reports what is actually resident
        # (pool_mb ≪ dense_mb once registered ≫ active cohort)
        "local_buf_mb": sim.n_params * n_clients
        * (2 if buffer_dtype == "bfloat16" else 4) / 2 ** 20,
        "state_capacity": state_capacity, "state_offload": state_offload,
        "store": sim.store.telemetry(),
        "accuracy": h.accuracy,
        "final_acc": h.accuracy[-1],
        "traffic_gb": h.traffic_bits[-1] / 8e9,
        "avg_waiting_s": h.waiting[-1],
        "wall_s": wall,
    })
    if compare_pipeline:
        del sim
        gc.collect()       # drop the measured run's buffers first
        sim_s = Simulator(build(False))
        h_sync = sim_s.run()
        out["sync_s_per_round"] = median_warm(h_sync)
        out["pipeline_speedup"] = out["sync_s_per_round"] / out["s_per_round"]
        out["pipeline_parity"] = {
            "max_acc_diff": max(abs(a - b) for a, b in
                                zip(h.accuracy, h_sync.accuracy)),
            "traffic_rel_diff": abs(h.traffic_bits[-1]
                                    - h_sync.traffic_bits[-1])
            / max(h.traffic_bits[-1], 1e-12)}
    return out


def _subprocess_point(extra_env=None, **kw) -> dict:
    """Run one point in a fresh interpreter; parse its JSON tail line.
    A parent that had started JAX could hold the accelerator the child
    needs, so the parent must not have imported it."""
    if "jax" in sys.modules:
        raise RuntimeError("the scale-study parent must stay off JAX")
    cmd = [sys.executable, str(Path(__file__).resolve()),
           "--point", json.dumps(kw)]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(extra_env or {})
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"scale point {kw} failed:\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _parity(a: dict, b: dict) -> dict:
    """Same-seed trajectory agreement between two points."""
    diffs = [abs(x - y) for x, y in zip(a["accuracy"], b["accuracy"])]
    return {"max_acc_diff": max(diffs),
            "traffic_rel_diff": abs(a["traffic_gb"] - b["traffic_gb"])
            / max(a["traffic_gb"], 1e-12)}


# same-seed runs must agree to eval quantization noise; CI fails above this
PARITY_ACC_TOL = 5e-3
PARITY_TRAFFIC_TOL = 1e-5
# sublinear-state gate: a 10× registered-client increase at the SAME active
# cohort may at most double peak RSS (pool + host maps, not O(n) buffers)
REGISTERED_RSS_RATIO_MAX = 2.0


def _registered_points(base: dict) -> tuple[list, dict]:
    """The registered-scale study (DESIGN.md §9): oppo_ts LR cohorts with a
    FIXED ~1k active cohort while the registered population grows 10×/100×.
    The grow-on-demand ClientStateStore keeps resident state keyed to
    participation, so peak RSS must track the cohort, not registration —
    the ratio between the points is the CI gate."""
    reg10k = _subprocess_point(n_clients=10_000, participation=0.1, **base)
    reg100k = _subprocess_point(n_clients=100_000, participation=0.01,
                                **base)
    summary = {
        "peak_rss_mb_10k": reg10k["peak_rss_mb"],
        "peak_rss_mb_100k": reg100k["peak_rss_mb"],
        "rss_ratio_100k_vs_10k": reg100k["peak_rss_mb"]
        / max(reg10k["peak_rss_mb"], 1e-9),
        "pool_mb_100k": reg100k["store"]["pool_mb"],
        "dense_mb_100k": reg100k["store"]["dense_mb"],
        "resident_100k": reg100k["store"]["resident"],
    }
    return [reg10k, reg100k], summary


def _tag(p: dict) -> str:
    chunk = ("auto" + str(p["chunk"]) if p["chunk_size"] is None
             else ("chunk" + str(p["chunk_size"]) if p["chunk_size"]
                   else "unchunked"))
    return (f"{p.get('dataset', 'har')}/n{p['n_clients']}/"
            f"P{p['participants']}/{chunk}"
            + ("/sync" if not p.get("pipelined", True) else "")
            + ("/masked" if not p.get("ragged", True) else "")
            + ("/bf16" if p.get("buffer_dtype") == "bfloat16" else "")
            + ("/dense-state" if p.get("state_capacity") == 0 else "")
            + (f"/cap{p['state_capacity']}" if p.get("state_capacity")
               else "")
            + (f"/{p['state_offload']}"
               if p.get("state_offload", "none") != "none" else "")
            + ("/sharded" if p["sharded"] else ""))


def scale_bench(smoke: bool = False) -> dict:
    results: dict = {"config": {"smoke": smoke, "dataset": "har"}}
    if smoke:   # CI: pipelined+auto-chunk path vs its sync/explicit twins
        # 4 rounds ⇒ 3 warm wall samples per driver — with fewer, the
        # overlap number is one noisy sample and meaningless even as smoke
        base = dict(rounds=4, participation=0.2, data_scale=0.25, tau=1,
                    n_clients=60)
        pipelined = _subprocess_point(chunk_size=None,
                                      compare_pipeline=True, **base)
        explicit = _subprocess_point(chunk_size=4, **base)
        masked = _subprocess_point(chunk_size=None, ragged=False, **base)
        # dense-state twin: state_capacity=0 pre-materializes every row —
        # slot indirection must be numerically invisible (bit-identical)
        dense_state = _subprocess_point(chunk_size=None, state_capacity=0,
                                        **base)
        reg_points, results["registered_scale"] = _registered_points(
            dict(dataset="oppo_ts", rounds=3, data_scale=0.05, tau=1,
                 chunk_size=None))
        # capped store under eviction pressure with the shadow-row probe
        # on: surfaces the ‖restored − true‖/‖true‖ centroid-approximation
        # telemetry (DESIGN.md §9) — a report, not a gate
        capped = _subprocess_point(chunk_size=None, state_capacity=16,
                                   measure_eviction_error=True, **base)
        results["eviction_error"] = capped["store"].get("restore_error")
        points = [pipelined, explicit, masked, dense_state, capped,
                  *reg_points]
        results["parity_pipelined_vs_sync"] = pipelined["pipeline_parity"]
        results["parity_auto_vs_explicit"] = _parity(pipelined, explicit)
        # the ragged-vs-masked gate (DESIGN.md §8): same plan, same sample
        # prefixes — drift beyond float-reduction noise fails CI
        results["parity_ragged_vs_masked"] = _parity(pipelined, masked)
        results["parity_pool_vs_dense"] = _parity(pipelined, dense_state)
    else:
        # Fig.-10-style 500/1000/2000 scale sweep (10% participation, now
        # pipelined + auto-chunk), plus a DENSE 1000-client cohort (50%
        # participation ⇒ P=500) measured two ways: synchronous-then-
        # pipelined back-to-back IN ONE subprocess (same auto chunk — the
        # sampling/step overlap is ~1% of a compute-bound dense round, so
        # cross-subprocess noise would bury it) and auto vs explicit
        # chunk=48-MB-budget (same-seed parity + RSS budget). At P=500 the
        # [P, n_params] round intermediates (~4×330 MB unchunked) dominate
        # the process baseline, so peak RSS shows what the auto-chunk
        # budget bounds. The [n, n_params] local buffer is O(n) by design,
        # reported separately as local_buf_mb.
        base = dict(rounds=4, participation=0.1)
        dense = dict(participation=0.5, n_clients=1000,
                     chunk_budget_mb=48.0)
        # identical rounds: _parity compares cumulative traffic at the end
        pipelined = _subprocess_point(chunk_size=None, rounds=6,
                                      compare_pipeline=True, **dense)
        explicit = _subprocess_point(chunk_size=25, rounds=6, **dense)
        masked_dense = _subprocess_point(chunk_size=None, rounds=6,
                                         ragged=False, **dense)
        n1000 = _subprocess_point(n_clients=1000, chunk_size=None, **base)
        # bf16 local-buffer storage at the 1000-client point: halves
        # local_buf_mb (the only O(n_clients) RSS term); accuracy delta
        # vs the f32 twin is the cost, reported below
        n1000_bf16 = _subprocess_point(n_clients=1000, chunk_size=None,
                                       buffer_dtype="bfloat16", **base)
        points = [
            _subprocess_point(n_clients=500, chunk_size=None, **base),
            n1000, n1000_bf16,
            _subprocess_point(n_clients=2000, chunk_size=None, **base),
            pipelined, explicit, masked_dense,
            # sharded: same 1000-client cohort over 4 forced host devices
            # (a forced host platform only exists on the CPU backend: on an
            # accelerator the child sees its own devices and n_dev=4 fails
            # the point rather than record a smaller mesh)
            _subprocess_point(
                n_clients=1000, chunk_size=None, sharded=True, n_dev=4,
                extra_env={"XLA_FLAGS":
                           "--xla_force_host_platform_device_count=4"},
                **base),
            # bigger model (cifar10 CNN) through the same pipelined +
            # auto-chunk path
            _subprocess_point(dataset="cifar10", n_clients=200,
                              chunk_size=None, rounds=3, participation=0.1,
                              data_scale=0.2, tau=2),
            # the ROADMAP-leftover speech point: full-cardinality 85k×4000-
            # sample clips, 35 classes — affordable now that execution is
            # plan-shaped (the b-spread cuts the conv-heavy training FLOPs)
            _subprocess_point(dataset="speech", n_clients=200,
                              chunk_size=None, rounds=3, participation=0.1,
                              data_scale=1.0, tau=2),
        ]
        # registered-scale study (DESIGN.md §9): 10k → 100k → 1M registered
        # clients at a fixed ~1k active cohort; resident state is
        # participation-keyed, so RSS stays flat while dense_mb grows 100×
        reg_base = dict(dataset="oppo_ts", rounds=3, data_scale=0.05,
                        tau=1, chunk_size=None)
        reg_points, results["registered_scale"] = _registered_points(
            reg_base)
        reg1m = _subprocess_point(n_clients=1_000_000, participation=0.001,
                                  **reg_base)
        results["registered_scale"].update({
            "peak_rss_mb_1m": reg1m["peak_rss_mb"],
            "rss_ratio_1m_vs_10k": reg1m["peak_rss_mb"]
            / max(results["registered_scale"]["peak_rss_mb_10k"], 1e-9),
            "pool_mb_1m": reg1m["store"]["pool_mb"],
            "dense_mb_1m": reg1m["store"]["dense_mb"],
        })
        # dense-state parity twin at the 1000-client point: slot
        # indirection must be numerically invisible at scale too
        n1000_dense_state = _subprocess_point(n_clients=1000,
                                              chunk_size=None,
                                              state_capacity=0, **base)
        points += [*reg_points, reg1m, n1000_dense_state]
        results["parity_pipelined_vs_sync"] = pipelined["pipeline_parity"]
        results["parity_auto_vs_explicit"] = _parity(pipelined, explicit)
        results["parity_ragged_vs_masked"] = _parity(pipelined, masked_dense)
        results["parity_pool_vs_dense"] = _parity(n1000, n1000_dense_state)
        results["pipeline_speedup_dense"] = pipelined["pipeline_speedup"]
        results["ragged_speedup_dense"] = (masked_dense["s_per_round"]
                                           / pipelined["s_per_round"])
        # bf16 storage trade at the 1000-client point (accuracy lists are
        # full trajectories; the delta is NOT a parity gate — bf16 is a
        # declared precision trade, not a semantics bug)
        results["bf16_local_buffer"] = {
            "local_buf_mb_f32": n1000["local_buf_mb"],
            "local_buf_mb_bf16": n1000_bf16["local_buf_mb"],
            "max_acc_diff": max(abs(a - b) for a, b in
                                zip(n1000["accuracy"],
                                    n1000_bf16["accuracy"])),
            "final_acc_f32": n1000["final_acc"],
            "final_acc_bf16": n1000_bf16["final_acc"],
        }
    for p in points:
        extra = (f";overlap={p['pipeline_speedup']:.3f}x"
                 f"(sync {p['sync_s_per_round']:.2f}s)"
                 if "pipeline_speedup" in p else "")
        if p.get("ragged", True):
            # tier occupancy + jit-cache size: shape explosions fail loudly
            occ = ",".join(f"{k}:{v}" for k, v in
                           p.get("tier_occupancy", {}).items())
            extra += (f";tiers=[{occ}];shapes="
                      f"{p['compiled_tier_shapes']}"
                      f"/{p['shape_lattice_bound']};"
                      f"work={p['work_fraction']:.2f}")
        st = p.get("store", {})
        if st:
            extra += (f";pool_mb={st['pool_mb']:.1f}"
                      f"(dense {st['dense_mb']:.1f});"
                      f"resident={st['resident']}/{st['registered']}")
        print(f"fig10_scale/{_tag(p)},{p['s_per_round'] * 1e6:.0f},"
              f"peak_rss_mb={p['peak_rss_mb']:.0f};"
              f"acc={p['final_acc']:.3f};wait_s={p['avg_waiting_s']:.1f}"
              + extra)
    results["points"] = points
    payload = json.dumps(results, indent=1, default=float)
    name = "BENCH_scale_smoke.json" if smoke else "BENCH_scale.json"
    (ROOT / name).write_text(payload)
    out2 = ROOT / "experiments" / "bench"
    out2.mkdir(parents=True, exist_ok=True)
    (out2 / name).write_text(payload)
    print(f"wrote {name}")
    # parity is a correctness gate, not a report: out-of-tolerance deltas
    # fail the run (CI runs --smoke and relies on this exit code) — AFTER
    # the JSON write above, so the measurements survive for debugging
    bad = {k: v for k, v in results.items() if k.startswith("parity_")
           and (v["max_acc_diff"] > PARITY_ACC_TOL
                or v["traffic_rel_diff"] > PARITY_TRAFFIC_TOL)}
    if bad:
        raise SystemExit(f"scale parity outside tolerance "
                         f"(acc>{PARITY_ACC_TOL} or "
                         f"traffic>{PARITY_TRAFFIC_TOL}): {bad}")
    # shape-explosion gate (same convention): a ragged point whose jit
    # cache exceeds the static lattice bound means tier shapes leaked
    # round-dependence. Shared with `python -m repro.analysis` — the same
    # contract check reads each point's telemetry dict.
    from repro.analysis.contracts import check_tier_shapes
    blown = [str(r) for p in points if p.get("ragged", True)
             for r in [check_tier_shapes(p, _tag(p))] if not r.ok]
    if blown:
        raise SystemExit("ragged jit cache exceeded the tier-lattice "
                         "bound: " + "; ".join(blown))
    # sublinear-state gate (DESIGN.md §9): peak RSS at 100k registered
    # clients must stay within REGISTERED_RSS_RATIO_MAX of the
    # same-active-cohort 10k control — superlinear growth means the store
    # leaked an O(n_clients) resident term
    ratio = results["registered_scale"]["rss_ratio_100k_vs_10k"]
    if ratio > REGISTERED_RSS_RATIO_MAX:
        raise SystemExit(
            f"peak RSS grew superlinearly with registered clients: "
            f"100k-vs-10k ratio {ratio:.2f} > {REGISTERED_RSS_RATIO_MAX} "
            f"({results['registered_scale']})")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", action="store_true",
                    help="run the 500/1000/2000-client engine scale study")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny scale study for CI")
    ap.add_argument("--point", type=str, default=None,
                    help="(internal) run one scale point from a JSON spec "
                         "and print the result JSON")
    args = ap.parse_args()
    if args.scale or args.smoke:
        # the parent only spawns the points: it stays off JAX so that each
        # child can take the accelerator
        scale_bench(smoke=args.smoke)
        return
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.point is not None:
        print(json.dumps(run_point(**json.loads(args.point)), default=float))
    else:
        run(log=print)


if __name__ == "__main__":
    main()
