"""Fault tolerance of the wire-boundary engine (DESIGN.md §11–§12).

Four studies:

* **fault grid** — dropout {0, 10, 30%} × Byzantine sign-flip {0, 10, 20%}
  × aggregator {mean, trimmed_mean, norm_clip}, every run through the
  serialized loopback wire. Emits ``BENCH_faults.json`` with full accuracy
  trajectories, modeled + measured (wire) traffic, and per-run fault
  totals from the simulator's fault log. The headline claim it documents:
  under a 10% sign-flip adversary plain mean collapses while trimmed-mean
  and norm-clip stay at (or above) mean's fault-free accuracy.
* **adversarial-availability frontier** — diurnal churn (fl/availability)
  × adaptive attack {support_poison, alie} × aggregator {mean,
  trimmed_mean, median, krum} on har: the attacks exploit the compressed
  top-k representation itself, and the frontier shows plain mean pulled
  ≥ 1 relative deviation from the fault-free global while an
  order-statistic aggregator holds ≤ 0.8 at ≤ 0.02 accuracy cost. Each
  run also reports the staleness distribution the churn induces — the
  input Caesar's §4.1 download policy keys compression off.
* **queue-transport load generator** — N producer processes encode
  realistic top-k uploads into a multiprocessing queue; the server drains
  and runs the fig-11 hot loop (``robust.decode_and_aggregate``: decode +
  CRC check + fold) under EVERY aggregation policy. Reports end-to-end
  and server-side uploads/s + MB/s per policy.
* **backpressured soak** — a sustained thousands-of-uploads run from
  multiple producers against a BOUNDED server queue: producers offer via
  ``wire.send_with_backoff`` (non-blocking try_send + exponential
  backoff), the server drains one-at-a-time while sampling queue depth.
  Emits backpressure telemetry: queue-depth profile, reject rate, retry
  counts, decode throughput, p50/p99 end-to-end upload latency.

``--smoke`` is the CI gate (tiny config, seconds): (a) a zero-fault
loopback run must be BIT-IDENTICAL to the in-process engine — accuracy
series, traffic accounting and the final global vector; (b) trimmed-mean
must neutralize a 10% sign-flip attack that measurably degrades plain
mean; (c) median and krum must be chunking-invariant BIT-exactly (the
same decoded row stream split at different chunk sizes yields the same
aggregate); (d) a short bounded-queue soak must deliver every accepted
upload exactly once with a bounded reject rate. Writes
``BENCH_faults_smoke.json`` (gitignored); the committed
``BENCH_faults.json`` comes from a full run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent

DROPOUTS = [0.0, 0.1, 0.3]
BYZANTINE = [0.0, 0.1, 0.2]
AGGREGATORS = ["mean", "trimmed_mean", "norm_clip"]
ATTACK_SCALE = 10.0

# adversarial-availability frontier (DESIGN.md §12)
FRONTIER_ATTACKS = ["support_poison", "alie"]
FRONTIER_AGGS = ["mean", "trimmed_mean", "median", "krum"]
FRONTIER_BYZ = 0.2
# support_poison's damage scales with the magnitude the attacker injects
# off-support (it controls its own payload, so nothing caps this): ×10
# only nudges the har mean ~0.6 deviation, ×30 drags it >10 while the
# order-statistic aggregators still see a majority of exact zeros on
# every junk coordinate (alie ignores this knob — its power is alie_z)
FRONTIER_SCALE = 30.0

# smoke gates, in PARAMETER space (the tiny config's 50-sample accuracy
# is too noisy to rank aggregators): relative to the fault-free global,
# the attacked-mean model must deviate by at least MEAN_DEVIATION_MIN
# while trimmed-mean stays under ROBUST_DEVIATION_MAX — and trimmed-mean
# must not give up accuracy vs the fault-free run
MEAN_DEVIATION_MIN = 1.0
ROBUST_DEVIATION_MAX = 0.8
ROBUST_ACC_TOL = 0.02
# smoke soak gate: with a queue bounded well below the offered load some
# rejects are EXPECTED (that is the point), but the producers' capped
# backoff must still land the large majority
SOAK_REJECT_MAX = 0.5


def _sim_cfg(smoke: bool, wire: str = "loopback",
             aggregation: str = "mean", faults=None, seed: int = 0,
             availability=None):
    from repro.core.caesar import CaesarConfig
    from repro.fl import availability as AV
    from repro.fl import faults as F
    from repro.fl.simulation import SimConfig
    if smoke:
        base = dict(dataset="oppo_ts", rounds=8, n_clients=12,
                    data_scale=0.01, eval_every=4, participation=0.5,
                    dataset_kwargs={"n_features": 64},
                    caesar=CaesarConfig(tau=2, b_max=8,
                                        use_error_feedback=True))
    else:
        base = dict(dataset="har", rounds=15, n_clients=30,
                    data_scale=0.05, eval_every=5, participation=0.2,
                    caesar=CaesarConfig(tau=3, b_max=16,
                                        use_error_feedback=True))
    return SimConfig(seed=seed, wire=wire, aggregation=aggregation,
                     faults=faults or F.FaultConfig(),
                     availability=availability or AV.AvailabilityConfig(),
                     **base)


def run_point(smoke: bool, dropout: float, byz: float, aggregation: str,
              seed: int = 0, log=lambda s: None) -> dict:
    from repro.fl import faults as F
    from repro.fl.simulation import Simulator
    fc = F.FaultConfig(dropout_rate=dropout, byzantine_frac=byz,
                       attack="sign_flip", attack_scale=ATTACK_SCALE)
    sim = Simulator(_sim_cfg(smoke, aggregation=aggregation, faults=fc,
                             seed=seed))
    t0 = time.perf_counter()
    h = sim.run(log=log)
    wall = time.perf_counter() - t0
    status = np.concatenate([e["status"] for e in sim.fault_log])
    return {
        "dropout": dropout, "byzantine": byz, "aggregation": aggregation,
        "accuracy": h.accuracy, "final_acc": h.accuracy[-1],
        "traffic_gb": h.traffic_bits[-1] / 8e9,
        "wire_mb": h.wire_bits[-1] / 8e6 if h.wire_bits else 0.0,
        "time_s": h.sim_time[-1],
        "n_uploads": int(np.sum(status != F.DROP)),
        "n_dropped": int(np.sum(status == F.DROP)),
        "n_byzantine": int(sum(e["byz"].sum() for e in sim.fault_log)),
        "n_crc_dropped": int(sum(e["n_crc_dropped"]
                                 for e in sim.fault_log)),
        "wall_s": wall,
    }


# ---------------------------------------------------------------------------
# adversarial-availability frontier (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _avail_summary(avail_log: list) -> dict:
    """Round-averaged staleness/eligibility telemetry from the driver's
    avail_log — the churn-induced distribution the download policy sees."""
    stats = [e["staleness"] for e in avail_log if e["staleness"].get("n")]
    out = {"n_forced_total": int(sum(e["n_forced"] for e in avail_log)),
           "n_eligible_mean": float(np.mean([e["n_eligible"]
                                             for e in avail_log]))}
    for q in ("mean", "p50", "p90", "p99"):
        out[f"staleness_{q}"] = float(np.mean([s[q] for s in stats]))
    out["staleness_max"] = float(max(s["max"] for s in stats))
    return out


def frontier_bench(smoke: bool = False, log=lambda s: None) -> dict:
    """Diurnal churn × adaptive attack × aggregator: for each attack, how
    far does each server policy let a 20% colluding adversary drag the
    global model from the fault-free (same-churn) trajectory, and at what
    accuracy cost? ``deviation`` is ‖g − g_clean‖/‖g_clean‖ against the
    fault-free mean run under the IDENTICAL availability schedule, so the
    metric isolates the attack, not the churn."""
    from repro.fl import availability as AV
    from repro.fl import faults as F
    from repro.fl.simulation import Simulator
    av = AV.AvailabilityConfig(kind="diurnal", day_rounds=4 if smoke else 6,
                               duty=0.5)

    def run(agg, attack, byz):
        fc = F.FaultConfig(byzantine_frac=byz, attack=attack,
                           attack_scale=FRONTIER_SCALE)
        sim = Simulator(_sim_cfg(smoke, aggregation=agg, faults=fc,
                                 availability=av))
        t0 = time.perf_counter()
        h = sim.run()
        return (np.asarray(sim.global_flat), {
            "aggregation": agg, "attack": attack, "byzantine": byz,
            "final_acc": h.accuracy[-1], "accuracy": h.accuracy,
            "wall_s": time.perf_counter() - t0,
            **_avail_summary(sim.avail_log)})

    g_clean, clean = run("mean", "sign_flip", 0.0)
    ref = float(np.linalg.norm(g_clean))
    points = [dict(clean, deviation=0.0)]
    for attack in FRONTIER_ATTACKS:
        for agg in FRONTIER_AGGS:
            g, p = run(agg, attack, FRONTIER_BYZ)
            p["deviation"] = float(np.linalg.norm(g - g_clean)) / ref
            points.append(p)
            log(f"fig11_frontier/{agg}/{attack},"
                f"{p['wall_s'] * 1e6:.0f},"
                f"acc={p['final_acc']:.3f};dev={p['deviation']:.2f};"
                f"stale_p90={p['staleness_p90']:.1f}")

    def cell(agg, attack):
        return next(p for p in points if p["aggregation"] == agg
                    and p["attack"] == attack)
    sp_mean = cell("mean", "support_poison")
    holders = [p for p in points
               if p["attack"] == "support_poison"
               and p["aggregation"] in ("median", "krum")
               and p["deviation"] <= ROBUST_DEVIATION_MAX
               and p["final_acc"] >= clean["final_acc"] - ROBUST_ACC_TOL]
    return {"clean_acc": clean["final_acc"],
            "availability": {"kind": av.kind, "day_rounds": av.day_rounds,
                             "duty": av.duty},
            "support_poison_mean_deviation": sp_mean["deviation"],
            "robust_holders": [p["aggregation"] for p in holders],
            "ok": bool(sp_mean["deviation"] >= MEAN_DEVIATION_MIN
                       and holders),
            "points": points}


# ---------------------------------------------------------------------------
# queue-transport load generator
# ---------------------------------------------------------------------------

def _no_jax_in_producer():
    """Producer processes are numpy-only: the server process holds the
    accelerator, and a child that started JAX would contend for it."""
    if "jax" in sys.modules:
        raise RuntimeError("a queue producer imported jax")


def _producer(queue, producer_id: int, n_uploads: int, n_params: int,
              k: int):
    """One producer process: encode + push ``n_uploads`` realistic top-k
    payloads. Top-level so multiprocessing's spawn can import it; only
    touches numpy-side modules (no jax in the producers)."""
    from repro.core import rng as RNG
    from repro.fl import wire as W
    _no_jax_in_producer()
    rng = RNG.stream(1234, RNG.KIND_FAULTS, 0, producer_id)
    for i in range(n_uploads):
        idx = rng.choice(n_params, size=k, replace=False).astype(np.int64)
        vals = rng.normal(0.0, 1e-2, size=k).astype(np.float32)
        queue.put(W.encode_upload(idx, vals, client=producer_id,
                                  round_=i, n_params=n_params))


def queue_throughput(n_producers: int = 3, uploads_per_producer: int = 32,
                     n_params: int = 1 << 17, topk_frac: float = 0.01,
                     aggregation: str = "mean") -> dict:
    """Hammer the server's decode+aggregate hot loop through a REAL
    multiprocessing queue, under any aggregation policy. End-to-end rate
    includes producer encode + queue transit; the server-side rate times
    only drain-to-aggregate."""
    import multiprocessing as mp

    from repro.fl import robust as RB
    from repro.fl import wire as W
    k = max(1, int(round(topk_frac * n_params)))
    ctx = mp.get_context("spawn")
    tr = W.QueueTransport(ctx=ctx)
    total = n_producers * uploads_per_producer
    agg = RB.make_aggregator(aggregation, cohort=total,
                             trim_frac=min(0.1, 1.0 / total))
    procs = [ctx.Process(target=_producer,
                         args=(tr.queue, i, uploads_per_producer,
                               n_params, k))
             for i in range(n_producers)]
    t0 = time.perf_counter()
    for p in procs:
        p.start()
    payloads = tr.drain(total, timeout=300)
    t_drained = time.perf_counter()
    delta, n_ok, n_bad = RB.decode_and_aggregate(payloads, n_params, agg)
    np.asarray(delta)
    t_done = time.perf_counter()
    for p in procs:
        p.join()
    tr.close()
    nbytes = sum(len(p) for p in payloads)
    assert n_ok == total and n_bad == 0, (n_ok, n_bad, total)
    server_s = t_done - t_drained
    e2e_s = t_done - t0
    return {
        "aggregation": aggregation,
        "n_producers": n_producers, "uploads": total,
        "n_params": n_params, "k": k,
        "payload_bytes": W.payload_nbytes(n_params, k),
        "total_mb": nbytes / 2 ** 20,
        "server_decode_agg_s": server_s,
        "server_uploads_per_s": total / max(server_s, 1e-9),
        "server_mb_per_s": nbytes / 2 ** 20 / max(server_s, 1e-9),
        "e2e_s": e2e_s,
        "e2e_uploads_per_s": total / max(e2e_s, 1e-9),
    }


# ---------------------------------------------------------------------------
# backpressured soak (DESIGN.md §12)
# ---------------------------------------------------------------------------

_DONE = b"SOAK-DONE:"


def _soak_producer(queue, results, producer_id: int, n_uploads: int,
                   n_params: int, k: int):
    """One soak producer: offer ``n_uploads`` payloads against the BOUNDED
    server queue via try_send + exponential backoff, recording per-upload
    send timestamps (wall time — matched server-side by the payload's
    (client, round) header) plus reject/retry/backoff totals. Finishes
    with a blocking sentinel so the server knows this producer drained."""
    from repro.core import rng as RNG
    from repro.fl import wire as W
    _no_jax_in_producer()
    tr = W.QueueTransport.attach(queue)
    rng = RNG.stream(4321, RNG.KIND_FAULTS, 1, producer_id)
    send_t = {}
    n_rej = n_retry = 0
    waited = 0.0
    for seq in range(n_uploads):
        idx = rng.choice(n_params, size=k, replace=False).astype(np.int64)
        vals = rng.normal(0.0, 1e-2, size=k).astype(np.float32)
        payload = W.encode_upload(idx, vals, client=producer_id,
                                  round_=seq, n_params=n_params)
        t_send = time.time()
        delivered, retries, w = W.send_with_backoff(tr, payload)
        n_retry += retries
        waited += w
        if delivered:
            send_t[seq] = t_send
        else:
            n_rej += 1
    queue.put(_DONE + str(producer_id).encode())   # blocking: always lands
    results.put({"producer": producer_id, "delivered": len(send_t),
                 "rejected": n_rej, "retries": n_retry,
                 "waited_s": waited, "send_t": send_t})


def upload_soak(n_producers: int = 4, uploads_per_producer: int = 600,
                n_params: int = 1 << 15, topk_frac: float = 0.01,
                maxsize: int = 64, aggregation: str = "mean") -> dict:
    """Sustained multi-producer soak against a bounded server queue.

    The server drains one payload at a time (sampling queue depth as it
    goes) until every producer's sentinel arrives — the queue is FIFO per
    producer, so all of a producer's accepted uploads precede its
    sentinel. Latency per upload is receive-wall minus the producer's
    send-wall (recorded BEFORE its backoff loop, so backoff waiting is
    inside the measured latency — that is the cost backpressure exacts),
    matched through the payload's (client=producer, round=seq) header.
    After the drain, the retained payloads replay through
    ``decode_and_aggregate`` for a clean decode-throughput figure."""
    import multiprocessing as mp

    from repro.fl import robust as RB
    from repro.fl import wire as W
    k = max(1, int(round(topk_frac * n_params)))
    ctx = mp.get_context("spawn")
    tr = W.QueueTransport(ctx=ctx, maxsize=maxsize)
    results = ctx.Queue()
    procs = [ctx.Process(target=_soak_producer,
                         args=(tr.queue, results, i, uploads_per_producer,
                               n_params, k))
             for i in range(n_producers)]
    t0 = time.perf_counter()
    for p in procs:
        p.start()
    payloads, recv = [], []
    depths = []
    n_done = 0
    while n_done < n_producers:
        payload = tr.get(timeout=300)
        if payload.startswith(_DONE):
            n_done += 1
            continue
        recv.append(time.time())
        depths.append(tr.depth())
        payloads.append(payload)
    drain_s = time.perf_counter() - t0
    stats = [results.get(timeout=60) for _ in range(n_producers)]
    for p in procs:
        p.join()
    tr.close()

    # latency: match each received payload back to its producer send time
    send_t = {s["producer"]: s["send_t"] for s in stats}
    t_dec0 = time.perf_counter()
    agg = RB.make_aggregator(aggregation, cohort=max(3, len(payloads)))
    delta, n_ok, n_bad = RB.decode_and_aggregate(payloads, n_params, agg)
    np.asarray(delta)
    decode_s = time.perf_counter() - t_dec0
    lat = []
    for payload, t_recv in zip(payloads, recv):
        u = W.decode_upload(payload)
        lat.append(t_recv - send_t[u.client][u.round])
    lat = np.asarray(lat) if lat else np.zeros(1)
    depths = np.asarray(depths) if depths else np.zeros(1)
    attempted = n_producers * uploads_per_producer
    delivered = int(sum(s["delivered"] for s in stats))
    rejected = int(sum(s["rejected"] for s in stats))
    return {
        "aggregation": aggregation,
        "n_producers": n_producers, "maxsize": maxsize,
        "n_params": n_params, "k": k,
        "attempted": attempted, "delivered": delivered,
        "received": len(payloads), "rejected": rejected,
        "reject_rate": rejected / max(attempted, 1),
        "retries": int(sum(s["retries"] for s in stats)),
        "backoff_wait_s": float(sum(s["waited_s"] for s in stats)),
        "drain_s": drain_s,
        "drain_uploads_per_s": len(payloads) / max(drain_s, 1e-9),
        "decode_agg_s": decode_s,
        "decode_uploads_per_s": n_ok / max(decode_s, 1e-9),
        "n_bad": n_bad,
        "queue_depth_mean": float(depths.mean()),
        "queue_depth_max": int(depths.max()),
        "latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "latency_p99_ms": float(np.percentile(lat, 99) * 1e3),
    }


# ---------------------------------------------------------------------------
# smoke gates (CI)
# ---------------------------------------------------------------------------

def smoke_bit_identity() -> dict:
    """Gate (a): zero faults through the serialized loopback wire must be
    bit-identical to the in-process engine."""
    from repro.fl.simulation import Simulator
    s0 = Simulator(_sim_cfg(True, wire="inproc"))
    h0 = s0.run()
    s1 = Simulator(_sim_cfg(True, wire="loopback"))
    h1 = s1.run()
    ok = (h0.accuracy == h1.accuracy
          and h0.traffic_bits == h1.traffic_bits
          and h0.sim_time == h1.sim_time
          and np.array_equal(np.asarray(s0.global_flat),
                             np.asarray(s1.global_flat)))
    return {"ok": bool(ok), "accuracy_inproc": h0.accuracy,
            "accuracy_loopback": h1.accuracy,
            "wire_mb": h1.wire_bits[-1] / 8e6}


def smoke_robust_aggregation() -> dict:
    """Gate (b): a 10% sign-flip adversary must yank the plain-mean model
    far from the fault-free trajectory, while trimmed-mean stays close to
    it AND holds the fault-free accuracy."""
    from repro.fl import faults as F
    from repro.fl.simulation import Simulator

    def final(aggregation, byz):
        fc = F.FaultConfig(byzantine_frac=byz, attack="sign_flip",
                           attack_scale=ATTACK_SCALE)
        sim = Simulator(_sim_cfg(True, aggregation=aggregation, faults=fc))
        h = sim.run()
        return np.asarray(sim.global_flat), h.accuracy[-1]

    g_clean, acc_clean = final("mean", 0.0)
    g_mean, acc_mean = final("mean", 0.1)
    g_trim, acc_trim = final("trimmed_mean", 0.1)
    ref = float(np.linalg.norm(g_clean))
    dev_mean = float(np.linalg.norm(g_mean - g_clean)) / ref
    dev_trim = float(np.linalg.norm(g_trim - g_clean)) / ref
    return {"ok": bool(dev_mean >= MEAN_DEVIATION_MIN
                       and dev_trim <= ROBUST_DEVIATION_MAX
                       and acc_trim >= acc_clean - ROBUST_ACC_TOL),
            "mean_clean_acc": acc_clean,
            "mean_attacked_acc": acc_mean,
            "trimmed_attacked_acc": acc_trim,
            "mean_deviation": dev_mean,
            "trimmed_deviation": dev_trim}


def smoke_chunking_invariance() -> dict:
    """Gate (c): every aggregator must give the same answer whatever chunk
    size the decoded row stream is split at — BIT-exact for the
    order-statistic aggregators (median, krum), whose finalize never sees
    chunk boundaries, and allclose for the streamed device folds."""
    from repro.core import rng as RNG
    from repro.fl import robust as RB
    from repro.fl import wire as W
    n_params, k, n_up = 1 << 12, 40, 23
    rng = RNG.stream(7, RNG.KIND_FAULTS, 0, 99)
    payloads = []
    for c in range(n_up):
        idx = rng.choice(n_params, size=k, replace=False).astype(np.int64)
        vals = rng.normal(0.0, 1e-2, size=k).astype(np.float32)
        payloads.append(W.encode_upload(idx, vals, client=c, round_=0,
                                        n_params=n_params))
    out = {"ok": True}
    from repro.fl.robust import AGGREGATIONS
    for name in AGGREGATIONS:
        deltas = []
        for chunk in (5, 16):
            agg = RB.make_aggregator(name, cohort=n_up)
            d, n_ok, n_bad = RB.decode_and_aggregate(payloads, n_params,
                                                     agg, chunk=chunk)
            assert n_ok == n_up and n_bad == 0, (name, n_ok, n_bad)
            deltas.append(np.asarray(d))
        exact = bool(np.array_equal(deltas[0], deltas[1]))
        close = bool(np.allclose(deltas[0], deltas[1],
                                 rtol=1e-5, atol=1e-7))
        out[name] = {"bit_exact": exact, "allclose": close}
        need_exact = name in ("median", "krum")
        out["ok"] = out["ok"] and (exact if need_exact else close)
    return out


def smoke_soak() -> dict:
    """Gate (d): a short soak against a queue bounded far below the
    offered load must (i) deliver exactly what the producers report
    delivered, (ii) decode every delivered payload, and (iii) keep the
    reject rate under SOAK_REJECT_MAX despite the pressure."""
    s = upload_soak(n_producers=2, uploads_per_producer=48,
                    n_params=1 << 13, maxsize=8)
    s["ok"] = bool(s["received"] == s["delivered"]
                   and s["n_bad"] == 0
                   and s["reject_rate"] <= SOAK_REJECT_MAX)
    return s


# ---------------------------------------------------------------------------

def fault_bench(smoke: bool = False) -> dict:
    results: dict = {"config": {"smoke": smoke,
                                "grid_attack": "sign_flip",
                                "frontier_attacks": FRONTIER_ATTACKS,
                                "attack_scale": ATTACK_SCALE,
                                "frontier_scale": FRONTIER_SCALE}}
    from repro.fl.robust import AGGREGATIONS
    if smoke:
        results["bit_identity"] = smoke_bit_identity()
        results["robust_aggregation"] = smoke_robust_aggregation()
        results["chunking_invariance"] = smoke_chunking_invariance()
        results["soak"] = smoke_soak()
        results["queue_throughput"] = [
            queue_throughput(n_producers=2, uploads_per_producer=8,
                             n_params=1 << 14, aggregation=agg)
            for agg in AGGREGATIONS]
        points = []
    else:
        points = []
        for agg in AGGREGATORS:
            for dr in DROPOUTS:
                for bz in BYZANTINE:
                    p = run_point(False, dr, bz, agg)
                    tag = f"{agg}/drop{dr:g}/byz{bz:g}"
                    print(f"fig11_faults/{tag},{p['wall_s'] * 1e6 / 15:.0f},"
                          f"acc={p['final_acc']:.3f};"
                          f"wire_mb={p['wire_mb']:.1f};"
                          f"dropped={p['n_dropped']};byz={p['n_byzantine']}")
                    points.append(p)
        results["frontier"] = frontier_bench(smoke=False, log=print)
        # every aggregation policy through the real mp-queue hot loop
        results["queue_throughput"] = [
            queue_throughput(aggregation=agg) for agg in AGGREGATIONS]
        # the sustained backpressure point: thousands of uploads against a
        # bounded ingress buffer
        results["soak"] = upload_soak()
        # the headline cells: does robust aggregation recover what the
        # adversary costs plain mean?
        def cell(agg, dr, bz):
            return next(p for p in points if p["aggregation"] == agg
                        and p["dropout"] == dr and p["byzantine"] == bz)
        base = cell("mean", 0.0, 0.0)["final_acc"]
        results["headline"] = {
            "mean_clean": base,
            "mean_byz10": cell("mean", 0.0, 0.1)["final_acc"],
            "trimmed_byz10": cell("trimmed_mean", 0.0, 0.1)["final_acc"],
            "norm_clip_byz10": cell("norm_clip", 0.0, 0.1)["final_acc"],
        }
    results["points"] = points
    payload = json.dumps(results, indent=1, default=float)
    name = "BENCH_faults_smoke.json" if smoke else "BENCH_faults.json"
    (ROOT / name).write_text(payload)
    out2 = ROOT / "experiments" / "bench"
    out2.mkdir(parents=True, exist_ok=True)
    (out2 / name).write_text(payload)
    print(f"wrote {name}")
    if not smoke:
        fr = results["frontier"]
        if not fr["ok"]:
            raise SystemExit(
                "adversarial-availability frontier gate failed (20% "
                f"support-poisoning must push plain mean >= "
                f"{MEAN_DEVIATION_MIN} relative deviation while at least "
                f"one of median/krum stays <= {ROBUST_DEVIATION_MAX} "
                f"within {ROBUST_ACC_TOL} accuracy of the fault-free "
                f"run): mean_dev={fr['support_poison_mean_deviation']:.2f} "
                f"holders={fr['robust_holders']}")
    if smoke:
        # gates AFTER the JSON write, so measurements survive a failure
        bi = results["bit_identity"]
        if not bi["ok"]:
            raise SystemExit(f"zero-fault loopback is NOT bit-identical "
                             f"to the in-process engine: {bi}")
        ra = results["robust_aggregation"]
        if not ra["ok"]:
            raise SystemExit(
                "robust-aggregation gate failed (10% sign-flip must push "
                f"plain mean >= {MEAN_DEVIATION_MIN} relative deviation "
                f"while trimmed-mean stays <= {ROBUST_DEVIATION_MAX} and "
                f"holds fault-free accuracy): {ra}")
        ci = results["chunking_invariance"]
        if not ci["ok"]:
            raise SystemExit(
                "chunking-invariance gate failed (median/krum must be "
                f"BIT-exact across chunk sizes): {ci}")
        sk = results["soak"]
        if not sk["ok"]:
            raise SystemExit(
                "soak gate failed (bounded-queue delivery must be exact "
                f"and reject rate <= {SOAK_REJECT_MAX}): {sk}")
        print(f"[gate] bit-identity OK; mean deviated "
              f"{ra['mean_deviation']:.2f} under attack, trimmed "
              f"{ra['trimmed_deviation']:.2f} at acc "
              f"{ra['trimmed_attacked_acc']:.3f} "
              f"(clean {ra['mean_clean_acc']:.3f}); chunking-invariance "
              f"OK; soak reject_rate={sk['reject_rate']:.2f} "
              f"p99={sk['latency_p99_ms']:.1f}ms OK")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: bit-identity, robust-aggregation, "
                         "chunking-invariance and bounded-queue soak "
                         "checks on a tiny config")
    args = ap.parse_args()
    fault_bench(smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


# the queue producers re-import this module under spawn; keep module-level
# work above limited to constants so that import stays cheap
