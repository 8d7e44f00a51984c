"""One cell of the benchmark: its files, the program's run, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in files of its own, found by name:

* ``configs/<config>.json``: the model and dataset sizes as run, the
  source, ``reduced`` and ``assumed``; ``configs/<config>.py`` beside it is
  the plain reference of the model (``init``, ``apply``).
* ``traffic/<traffic>.json``: the federation a cell runs (clients,
  participation, local iterations, batch caps, pool, wire, aggregation,
  eval cadence) and the reference's own settings.
* ``limits/<workload>.json``: the limit of each number the check compares.
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None``.

A workload is ``BENCHMARK.json``'s entry naming a config and a traffic.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# numbers of the check compared exactly, limit 0
EXACT = ("data_mismatch", "init_mismatch", "tier_mismatch")
CHECK_ROUNDS = 3
# rounds the set-up replays to time a warm round
REPLAY_ROUNDS = 5


class CellError(RuntimeError):
    """A cell that cannot be run as its files describe it."""


# -- files, by name ----------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise CellError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    model: object            # the configuration's plain reference module
    bench: dict              # BENCHMARK.json
    root: Path               # the directory holding BENCHMARK.json

    @property
    def chips(self) -> int:
        return int(next(w for w in self.bench["workloads"]
                        if w["name"] == self.name)["chips"])

    @property
    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = HERE) -> Cell:
    """The workload ``name`` of ``root/BENCHMARK.json`` with its files from
    ``bench_dir``."""
    bench = load_json(root / "BENCHMARK.json")
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if not wl:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    cfg_name, traffic = wl["config"], wl["traffic"]
    return Cell(
        name=name,
        config=load_json(bench_dir / "configs" / f"{cfg_name}.json"),
        traffic=load_json(bench_dir / "traffic" / f"{traffic}.json"),
        limits=load_json(bench_dir / "limits" / f"{name}.json"),
        model=load_module(bench_dir / "configs" / f"{cfg_name}.py",
                          f"chipbench_config_{cfg_name}"),
        bench=bench, root=root)


def load_metric(name: str, bench_dir: Path = HERE):
    return load_module(bench_dir / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}")


# -- the program -------------------------------------------------------------

def program_path(root: Path) -> None:
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def sim_config(cell: Cell, seed: int, rounds: int):
    """The program's SimConfig, every setting stated by the cell's files."""
    from repro.core.caesar import CaesarConfig
    from repro.fl import faults as F
    from repro.fl.simulation import SimConfig
    from repro.optim.sgd import SGDConfig
    t = cell.traffic
    return SimConfig(
        **cell.config["sim"], **t["sim"], seed=seed, rounds=rounds,
        caesar=CaesarConfig(**t["caesar"]), sgd=SGDConfig(**t["sgd"]),
        faults=F.FaultConfig(**t.get("faults", {})))


class CompileCounter:
    """Counts traces and backend compiles (persistent-cache loads too)
    while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.active = False
        self.count = 0
        self.seconds = 0.0
        self.names = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1
            self.seconds += duration
            self.names.append(kw.get("fun_name"))


def _run(sim, rounds: int, log=None):
    """One ``Simulator.run()`` of ``rounds`` rounds from round 1."""
    sim.reset()
    sim.cfg = dataclasses.replace(sim.cfg, rounds=rounds)
    return sim.run(log=log) if log is not None else sim.run()


def _occupancy(sim) -> dict:
    return dict(sim.executor.telemetry()["tier_occupancy"])


class RoundTap:
    """Reads what each round of ``Simulator.run()`` hands back to its
    round loop: the new global model, per-participant traffic, the tiers the
    executor ran, the wall time between rounds and the compiles inside
    each. Installed on the round call the loop makes (the executor's
    ``step_ragged``, or the wire engine's ``_wire_round``) for the set-up
    run only; it copies the outputs of the first ``keep`` rounds."""

    def __init__(self, sim, counter, keep: int = CHECK_ROUNDS):
        self.sim, self.counter, self.keep = sim, counter, keep
        self.owner, self.attr = ((sim, "_wire_round") if sim._wire_on
                                 else (sim.executor, "step_ragged"))
        self.orig = getattr(self.owner, self.attr)
        self.globals = [np.asarray(sim.flat0).copy()]
        self.bits = 0.0
        self.walls, self.compiles = [], []
        self.occ0 = _occupancy(sim)
        self.tiers = {}
        self._t = self._c = None
        setattr(self.owner, self.attr, self)

    def __call__(self, *args, **kw):
        if self._t is None:
            self._t, self._c = time.perf_counter(), self.counter.count
        out = self.orig(*args, **kw)
        if len(self.globals) <= self.keep:
            self.globals.append(np.asarray(out[0]).copy())
            self.bits += float(np.sum(out[1]) + np.sum(out[2]))
            if len(self.globals) == self.keep + 1:
                occ = _occupancy(self.sim)
                self.tiers = {k: occ[k] - self.occ0.get(k, 0) for k in occ
                              if occ[k] != self.occ0.get(k, 0)}
        now = time.perf_counter()
        self.walls.append(now - self._t)
        self.compiles.append(self.counter.count - self._c)
        self._t, self._c = now, self.counter.count
        return out

    def remove(self) -> dict:
        setattr(self.owner, self.attr, self.orig)
        return {"globals": self.globals, "bits": self.bits,
                "tiers": self.tiers}

    def mean_round_s(self) -> float:
        """Mean wall from one round's call to the next."""
        w = self.walls[1:] or self.walls
        return float(sum(w) / len(w))


def window_rounds(cell: Cell, seconds: float, round_s: float) -> int:
    """Rounds in the window: enough to fill ``seconds`` at ``round_s``."""
    n = math.ceil(seconds / max(round_s, 1e-3))
    return int(min(max(n, 2), cell.traffic["max_rounds"]))


def run_program(cell: Cell, seed: int, seconds: float, trace_dir=None,
                patch=None, t_start=None) -> dict:
    """Set-up, warm-up and the measured window of one run.

    Set-up builds the Simulator and drives it by ``run()`` through
    ``warmup_rounds`` rounds from the seed, reading rounds 1..3 for the
    check. A replay of the first rounds, every shape of them compiled by
    then, times a warm round and what a ``run()`` costs besides its
    rounds, which size the window; a ``run()`` of the window's rounds
    follows where the window holds more than the warm-up, so every shape
    is compiled (or loaded from the persistent cache) before it. The
    window is one more ``run()`` of the same rounds, which replays the
    same plans. ``patch(sim)``, where
    given, changes the Simulator after it is built (the tests use it to
    break the timed path)."""
    import jax
    t0 = time.perf_counter() if t_start is None else t_start
    from repro.fl.simulation import Simulator
    counter = CompileCounter()
    counter.active = True
    warm = max(int(cell.traffic["warmup_rounds"]), CHECK_ROUNDS)
    sim = Simulator(sim_config(cell, seed, warm))
    if patch is not None:
        patch(sim)
    tap = RoundTap(sim, counter)
    w0 = time.perf_counter()
    _run(sim, warm)
    warm_s = time.perf_counter() - w0
    chk = tap.remove()
    # the first rounds again, compiled by now: a warm round, and what a
    # run() costs besides its rounds (the pool it builds, the first
    # prefetch), so that the window lasts about ``seconds``
    replay = min(warm, REPLAY_ROUNDS)
    c0 = counter.count
    tap = RoundTap(sim, counter, keep=0)
    w0 = time.perf_counter()
    _run(sim, replay)
    replay_s = time.perf_counter() - w0
    counter_replay = counter.count - c0
    tap.remove()
    r = tap.mean_round_s()
    over = max(replay_s - sum(tap.walls), 0.0)
    n = window_rounds(cell, max(seconds - over, r), r)
    if n > warm:
        _run(sim, n)                        # every shape of rounds 1..n
    traced = {}

    def start_trace(round0: int):
        # host runtime events only: the Python tracer slows the host loop
        # many times over and would make the device look idle
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        traced["t0"] = time.perf_counter()
        traced["round0"] = round0

    ev = int(cell.traffic["sim"]["eval_every"])

    def log(_msg):
        # called at each eval boundary: the trace starts at the last one
        # that leaves a whole eval period (5 to 9 rounds at eval_every 5)
        if trace_dir is not None and not traced and sim._t_done > n - 2 * ev:
            start_trace(sim._t_done)

    occ0 = _occupancy(sim)
    shapes0 = sim.executor.telemetry()["compiled_tier_shapes"]
    setup_s = time.perf_counter() - t0
    counter.count, counter.seconds, counter.names = 0, 0.0, []
    if trace_dir is not None and n <= ev:
        start_trace(0)
    w0 = time.perf_counter()
    _run(sim, n, log=log)
    jax.effects_barrier()
    window_s = time.perf_counter() - w0
    counter.active = False
    if traced:
        traced["host_window_s"] = time.perf_counter() - traced["t0"]
        jax.profiler.stop_trace()
        traced["rounds"] = n - traced["round0"]
    tel = sim.executor.telemetry()
    executed = sum(_occupancy(sim).values()) - sum(occ0.values())
    faults = _fault_counts(sim)
    attempted = n * sim.n_part
    lost = attempted - executed if not faults else \
        attempted - faults["aggregated"] - faults["injected"]
    dev = jax.devices()
    result = {
        "setup_s": setup_s, "round_s": window_s / n, "window_s": window_s,
        "rounds": n, "attempted": attempted, "failed": max(lost, 0),
        "compiles_in_window": counter.count,
        "compile_s_in_window": counter.seconds,
        "new_tier_shapes_in_window": tel["compiled_tier_shapes"] - shapes0,
        "compiled_in_window": counter.names,
        "telemetry": tel, "faults": faults,
        "memory_peak_bytes": int(max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in dev)),
        "check": chk, "traced": traced,
        "warmup": {"rounds": warm, "seconds": warm_s,
                   "replay_rounds": replay, "replay_s": replay_s,
                   "replay_compiles": counter_replay, "round_s": r,
                   "run_overhead_s": over},
        "data_digest": _program_digest(sim),
    }
    del sim, tap
    gc.collect()
    return result


def _fault_counts(sim) -> dict:
    if not getattr(sim, "fault_log", None):
        return {}
    from repro.fl import faults as F
    inj = agg = 0
    for e in sim.fault_log:
        inj += int(np.sum(e["status"] != F.OK))
        agg += int(e["n_aggregated"])
    return {"injected": inj, "aggregated": agg}


def _program_digest(sim) -> dict:
    from chipbench.reference import data as D
    d = sim.data
    return {"x_train": D.digest(d.x_train), "y_train": D.digest(d.y_train),
            "x_test": D.digest(d.x_test), "y_test": D.digest(d.y_test),
            "splits": D.digest(sim._split_off, sim._split_idx)}


# -- the reference and the numbers compared ----------------------------------

def settings(cell: Cell):
    from chipbench.reference.caesar import Settings
    t, c = cell.traffic, cell.traffic["caesar"]
    f = t.get("faults", {})
    unsupported = {k: v for k, v in f.items()
                   if k not in ("dropout_rate", "byzantine_frac",
                                "attack", "attack_scale") and v}
    if unsupported or f.get("attack", "sign_flip") != "sign_flip":
        raise CellError(f"the reference has no model of the faults {f}")
    n = int(t["sim"]["n_clients"])
    return Settings(
        n_clients=n,
        n_part=max(1, int(round(t["sim"]["participation"] * n))),
        tau=int(c["tau"]), b_max=int(c["b_max"]), b_min=int(c["b_min"]),
        theta_d_max=float(c["theta_d_max"]),
        theta_u_min=float(c["theta_u_min"]),
        theta_u_max=float(c["theta_u_max"]), lam=float(c["lam"]),
        n_clusters=int(c["n_clusters"]), lr=float(t["sgd"]["lr"]),
        lr_decay=float(t["sgd"]["decay"]),
        dropout_rate=float(f.get("dropout_rate", 0.0)),
        byzantine_frac=float(f.get("byzantine_frac", 0.0)),
        attack_scale=float(f.get("attack_scale", 10.0)),
        aggregation=t["sim"]["aggregation"],
        trim_frac=float(t["sim"].get("trim_frac", 0.1)))


def reference_data(cell: Cell, seed: int):
    from chipbench.reference import data as D
    sim = cell.config["sim"]
    data = D.make_dataset(sim["dataset"], seed, float(sim["data_scale"]))
    splits, label_dist, volumes = D.dirichlet_partition(
        data[1], int(cell.traffic["sim"]["n_clients"]),
        float(cell.traffic["sim"]["p_heterogeneity"]), seed)
    digests = {"x_train": D.digest(data[0]), "y_train": D.digest(data[1]),
               "x_test": D.digest(data[2]), "y_test": D.digest(data[3]),
               "splits": D.split_digest(splits)}
    return data, splits, label_dist, volumes, digests


def reference_rounds(cell: Cell, seed: int, refdata, mode="reference",
                     fault="none", prog_globals=None,
                     k: int = CHECK_ROUNDS) -> dict:
    """Rounds 1..k of the plain reference: the global model after each,
    the traffic, the tiers its plans fall in, and the loss of each round's
    global model; with ``prog_globals``, the losses of those too."""
    from chipbench.reference.caesar import Reference
    data, splits, label_dist, volumes, _ = refdata
    ref = Reference(settings(cell), seed, cell.model, data, splits,
                    label_dist, volumes, mode=mode, fault=fault,
                    chunk=int(cell.traffic["reference_chunk"]))
    globals_ = [np.asarray(ref.global_f)]
    for t in range(1, k + 1):
        globals_.append(np.asarray(ref.run_round(t)))
    ref.locals.clear()
    tiers = {f"b{b}xt{tau}": n for (b, tau), n in sorted(ref.tiers.items())}
    loss = _loss_fn(cell, ref.unflatten, data)
    out = {"globals": globals_, "bits": ref.bits, "tiers": tiers,
           "sizes": ref.sizes, "losses": [loss(g) for g in globals_[1:]]}
    if prog_globals is not None:
        out["prog_losses"] = [loss(g) for g in prog_globals[1:]]
    return out


def _loss_fn(cell: Cell, unflatten, data):
    """Cross-entropy of a flat model on the first test samples, by the
    reference's forward pass at HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    m = int(cell.config["loss_samples"])
    x, y = jnp.asarray(data[2][:m]), jnp.asarray(data[3][:m])

    @jax.jit
    def loss(flat):
        logits = cell.model.apply(unflatten(flat), x)
        ll = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None],
                                 axis=-1)
        return -jnp.mean(ll)

    return lambda g: float(loss(jnp.asarray(g)))


def planned_samples(cell: Cell, seed: int, refdata, first: int,
                    last: int) -> int:
    from chipbench.reference.caesar import planned_samples as ps
    data, splits, label_dist, volumes, _ = refdata
    return ps(settings(cell), seed, splits, label_dist, volumes,
              float(cell.config["n_params"] * 32), first, last)


def forward_flops(cell: Cell, refdata) -> int:
    import jax
    from chipbench.flops import forward_flops_per_sample
    params = jax.eval_shape(
        lambda k: cell.model.init(k, n_classes=int(refdata[0][1].max()) + 1),
        jax.random.PRNGKey(0))
    return forward_flops_per_sample(cell.model.apply, params,
                                    cell.config["sample_shape"])


def leaf_norms(vec: np.ndarray, sizes: list) -> np.ndarray:
    bounds = np.cumsum([0] + list(sizes))
    return np.array([np.linalg.norm(vec[a:b].astype(np.float64))
                     for a, b in zip(bounds[:-1], bounds[1:])])


def leaf_gaps(prog_change, ref_change, sizes, keep) -> np.ndarray:
    """Per kept leaf, |‖p‖ − ‖r‖| / max(‖r‖, median leaf ‖r‖)."""
    p = leaf_norms(prog_change, sizes)
    r = leaf_norms(ref_change, sizes)
    return (np.abs(p - r) / np.maximum(r, np.median(r)))[keep]


def leaf_diffs(prog_change, ref_change, sizes, keep) -> np.ndarray:
    """Per kept leaf, ‖p − r‖ / max(‖r‖, median leaf ‖r‖): unlike the gap
    of norms, it sees rounding noise that leaves a leaf's norm as it is."""
    d = leaf_norms(prog_change - ref_change, sizes)
    r = leaf_norms(ref_change, sizes)
    return (d / np.maximum(r, np.median(r)))[keep]


def readings(prog: dict, ref: dict, prog_losses: list) -> dict:
    """Every number the check can compare, program against reference.
    A cell compares those its limits file names, and the exact ones."""
    sizes = ref["sizes"]
    pg, rg = prog["globals"], ref["globals"]
    r1 = leaf_norms(rg[0] - rg[1], sizes)
    # leaves the first round leaves unmoved to rounding in the reference
    keep = r1 >= 1e-3 * np.median(r1)
    g1 = leaf_gaps(pg[0] - pg[1], rg[0] - rg[1], sizes, keep)
    g3 = leaf_gaps(pg[0] - pg[-1], rg[0] - rg[-1], sizes, keep)
    d1 = leaf_diffs(pg[0] - pg[1], rg[0] - rg[1], sizes, keep)
    losses = [abs(a - b) / b for a, b in zip(prog_losses, ref["losses"])]
    return {
        "loss_gap": max(losses), "loss1_gap": losses[0],
        "update1_gap": float(np.max(g1)),
        "update1_median_gap": float(np.median(g1)),
        "change3_gap": float(np.max(g3)),
        "change3_median_gap": float(np.median(g3)),
        "update1_median_diff": float(np.median(d1)),
        "bits_gap": abs(prog["bits"] - ref["bits"]) / ref["bits"],
        "tier_mismatch": sum(abs(prog["tiers"].get(k, 0)
                                 - ref["tiers"].get(k, 0))
                             for k in set(prog["tiers"]) | set(ref["tiers"])),
    }


def all_readings(prog: dict, refdata, ref: dict) -> dict:
    """``readings`` and the exact numbers. ``ref`` holds the losses of the
    program's check rounds (``reference_rounds(prog_globals=...)``)."""
    nums = readings(prog["check"], ref, ref["prog_losses"])
    nums["data_mismatch"] = sum(prog["data_digest"][k] != refdata[4][k]
                                for k in refdata[4])
    nums["init_mismatch"] = int(np.sum(prog["check"]["globals"][0]
                                       != ref["globals"][0]))
    return {k: float(v) for k, v in nums.items()}


def check(cell: Cell, nums: dict) -> dict:
    """The numbers the cell compares, each with its limit."""
    out = {k: {"value": nums[k], "limit": float(lim)}
           for k, lim in cell.limits.items()}
    out.update({k: {"value": nums[k], "limit": 0} for k in EXACT})
    return out


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
