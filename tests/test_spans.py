"""Host spans of the round loop (repro.fl.spans) in a real profiler trace:
a tiny HAR Simulator on the CPU, traced as the chip benchmark traces it
(host runtime events on, Python tracer off). Every span lands with its
round, nests on its thread, and the executor's counts agree with its own
work counter."""
from __future__ import annotations

import collections
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.spans import load  # noqa: E402
from repro.core.caesar import CaesarConfig
from repro.fl.faults import FaultConfig
from repro.fl.simulation import SimConfig, Simulator
from repro.fl.spans import SPANS

ROUNDS, EVERY = 3, 2
MAIN = {"round", "round.wait_prefetch", "round.setup", "exec.prepare",
        "exec.dispatch", "exec.readback", "round.account"}
WORKER = {"prefetch", "prefetch.gather"}
WIRE = {"wire.collect", "wire.encode", "wire.decode", "agg.host"}

CASES = {
    # one (b, τ) tier, so executed rows × τ × b is the executor's work
    "inproc": dict(caesar=CaesarConfig(tau=2, b_max=4, b_min=4)),
    "loopback": dict(caesar=CaesarConfig(tau=2, b_max=8), wire="loopback",
                     aggregation="trimmed_mean", trim_frac=0.125,
                     faults=FaultConfig(dropout_rate=0.2,
                                        byzantine_frac=0.2)),
    "masked": dict(caesar=CaesarConfig(tau=2, b_max=8), ragged=False),
}
EXPECT = {
    "inproc": MAIN | WORKER | {"prefetch.plan"},
    "loopback": MAIN | WORKER | WIRE | {"prefetch.plan"},
    "masked": MAIN | WORKER | {"round.plan"},
}


def _spans(path) -> list:
    """Per host line, its program spans: (name, start, end, stats), as
    the benchmark's reader loads them."""
    return [[(e.name, e.start, e.end, e.stats) for e in line]
            for line in load(path)[1]]


def _run_traced(case, logdir):
    cfg = SimConfig(dataset="har", n_clients=12, participation=0.5,
                    rounds=ROUNDS, eval_every=EVERY, data_scale=0.2,
                    backend="jnp", eval_samples=64, **CASES[case])
    sim = Simulator(cfg)
    work = {}
    name = ("step_ragged" if cfg.wire == "inproc"
            else "step_ragged_deferred")
    step = getattr(sim.executor, name)

    def counted(*a, t=0, **kw):
        w0 = sim.executor.work_ragged
        out = step(*a, t=t, **kw)
        work[t] = sim.executor.work_ragged - w0
        return out

    setattr(sim.executor, name, counted)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        sim.run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    return case, sim, work, _spans(path)


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    """case -> (case, sim, work per round, spans per line), each case run
    once per module."""
    cache = {}

    def get(case):
        if case not in cache:
            cache[case] = _run_traced(
                case, tmp_path_factory.mktemp(f"trace_{case}"))
        return cache[case]

    return get


@pytest.fixture(params=sorted(CASES))
def traced(request, traces):
    return traces(request.param)


def test_every_span_appears_with_its_round(traced):
    case, sim, _, lines = traced
    seen = collections.defaultdict(set)
    per_round = collections.Counter()
    for line in lines:
        for name, _, _, st in line:
            seen[name].add(st["round"])
            per_round[st["round"]] += 1
    # one span per phase, none per client or chunk
    assert max(per_round.values()) <= 20
    assert set(seen) == EXPECT[case] | {"round.eval"}
    assert set(seen) <= set(SPANS)
    everyround = set(range(1, ROUNDS + 1))
    for name in EXPECT[case]:
        assert seen[name] == everyround, name
    assert seen["round.eval"] == {t for t in everyround
                                  if t % EVERY == 0 or t == ROUNDS}


def test_spans_nest_on_their_threads(traced):
    case, _, _, lines = traced
    by_name = {}
    for i, line in enumerate(lines):
        for name, s, e, st in line:
            by_name.setdefault(name, []).append((i, s, e, st["round"]))
    main = {i for i, *_ in by_name["round"]}
    worker = {i for i, *_ in by_name["prefetch"]}
    assert len(main) == 1 and len(worker) == 1 and main != worker
    parents = {n: ("prefetch" if n.startswith("prefetch.") else "round")
               for n in EXPECT[case] if n not in ("round", "prefetch")}
    for child, parent in parents.items():
        for i, s, e, t in by_name[child]:
            assert any(j == i and ps <= s and e <= pe and pt == t
                       for j, ps, pe, pt in by_name[parent]), (child, t)


def _dispatch(lines) -> dict:
    return {st["round"]: st for line in lines
            for name, _, _, st in line if name == "exec.dispatch"}


def test_dispatch_counts_hold_the_cohort(traced):
    _, sim, _, lines = traced
    disp = _dispatch(lines)
    assert sorted(disp) == list(range(1, ROUNDS + 1))
    for st in disp.values():
        assert st["valid"] == sim.n_part
        assert st["valid"] <= st["rows"]
        assert set(st) == {"round", "rows", "valid"}


def test_dispatch_rows_match_the_executors_work(traces):
    """One (b, τ) tier: the executed rows × τ × b of each round is the
    growth of ``work_ragged`` over it."""
    _, _, work, lines = traces("inproc")
    tau, b = 2, 4
    assert {t: st["rows"] * tau * b
            for t, st in _dispatch(lines).items()} == work


def test_masked_dispatch_rows_are_the_padded_cohort(traces):
    _, sim, _, lines = traces("masked")
    assert all(st["rows"] == sim.executor.n_dev * sim.executor.p_pad
               for st in _dispatch(lines).values())


def test_wire_spans_follow_the_fault_log(traces):
    """Each round the fault log records runs the wire's phases once, in
    order on the main line, and they carry no counts: the wire's counts
    are the fault log's."""
    _, sim, _, lines = traces("loopback")
    by = collections.defaultdict(dict)
    for line in lines:
        for name, s, e, st in line:
            if name in WIRE:
                assert set(st) == {"round"}, name
                assert st["round"] not in by[name], name
                by[name][st["round"]] = (s, e)
    rounds = [e["round"] for e in sim.fault_log]
    assert rounds == list(range(1, ROUNDS + 1))
    order = ("wire.collect", "wire.encode", "wire.decode", "agg.host")
    for t in rounds:
        ends = [by[n][t] for n in order]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:])), t
