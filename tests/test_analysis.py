"""Tests for the invariant-checker suite (repro.analysis).

Three layers: every REPxxx lint rule against its must-fail/must-pass
fixture twins (tests/fixtures/analysis/), the jaxpr/HLO contract checks
(including a deliberately un-donated step that must FAIL the donation
contract), and the pipeline ownership audit (clean run + detected
rogue-thread store touch). Plus the self-clean gate: the shipped source
tree lints clean, which pins the real violations this suite found.
"""
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.lint import SourceFile, lint_source, run_lint
from repro.analysis.ownership import audit_run
from repro.analysis.rules import ALL_RULES
from repro.core import rng as RNG

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "analysis"
REPO = pathlib.Path(__file__).resolve().parents[1]

# REP005 is scoped to device-math modules, REP009 to the wire/fault
# modules and REP010 to the availability schedule; their fixtures are
# linted under synthetic in-scope paths
_LINT_PATH = {"REP005": "src/repro/core/{name}",
              "REP009": "src/repro/fl/faults.py",
              "REP010": "src/repro/fl/availability.py"}


def _lint_fixture(code: str, which: str):
    name = f"{code.lower()}_{which}.py"
    path = _LINT_PATH.get(code, "{name}").format(name=name)
    src = SourceFile(path, (FIXTURES / name).read_text())
    diags, _ = lint_source(src, ALL_RULES)
    return diags


@pytest.mark.parametrize("code", [r.code for r in ALL_RULES])
def test_rule_flags_must_fail_fixture(code):
    diags = _lint_fixture(code, "fail")
    assert any(d.rule == code for d in diags), \
        f"{code} did not flag its must-fail fixture: {diags}"
    for d in diags:
        assert d.line > 0 and d.path


@pytest.mark.parametrize("code", [r.code for r in ALL_RULES])
def test_must_pass_fixture_is_clean(code):
    diags = _lint_fixture(code, "pass")
    assert diags == [], \
        f"must-pass fixture for {code} was flagged: {diags}"


def test_noqa_suppresses_one_code():
    text = (FIXTURES / "rep002_fail.py").read_text()
    noqa = text.replace(
        "np.random.default_rng(derived)",
        "np.random.default_rng(derived)  # repro: noqa=REP002")
    src = SourceFile("x.py", noqa)
    diags, suppressed = lint_source(src, ALL_RULES)
    assert suppressed == 1
    # the un-annotated line still fires
    assert any(d.rule == "REP002" for d in diags)


def test_bare_noqa_suppresses_all_codes():
    src = SourceFile("x.py", "import numpy as np\n"
                     "r = np.random.default_rng(7)  # repro: noqa\n")
    diags, suppressed = lint_source(src, ALL_RULES)
    assert diags == [] and suppressed == 1


def test_shipped_tree_lints_clean():
    """Pins the real REPxxx violations fixed in this PR (root RNG streams
    in data/synthetic, data/partition, fl/capability; per-round syncs in
    benchmarks and launch/train)."""
    paths = [REPO / p for p in ("src", "benchmarks", "examples")]
    diags, _ = run_lint([p for p in paths if p.exists()], root=REPO)
    assert diags == [], "\n".join(str(d) for d in diags)


_SPAN_LOOP = """
from repro.fl.spans import span


class Runner:
    def run(self, rounds, global_f, parts):
        for t in range(rounds):
            global_f, bits = self.step(t, global_f, parts)
            with span("round.account", round=t, {arg}) as sp:
                sp.set_metadata(rows={rows})
"""


@pytest.mark.parametrize("arg,rows,flagged", [
    ("bits=bits", "len(parts)", True),
    ("parts=len(parts)", "bits.sum()", True),
    ("parts=len(parts)", "len(parts)", False)])
def test_rep006_span_arguments_are_sinks(arg, rows, flagged):
    """A span argument derived from a device value in the round loop
    would sync the traced round only; a host count is clean."""
    src = SourceFile("x.py", _SPAN_LOOP.format(arg=arg, rows=rows))
    diags, _ = lint_source(src, ALL_RULES)
    assert any(d.rule == "REP006" for d in diags) == flagged, diags


# --- the fixed streams actually decorrelated --------------------------------

def test_rng_kinds_decorrelate_streams():
    draws = {kind: RNG.stream(0, kind).random()
             for kind in (RNG.KIND_CAP_TIER, RNG.KIND_DATASET,
                          RNG.KIND_PARTITION)}
    assert len(set(draws.values())) == len(draws), draws
    # and the pre-fix failure mode really was aliasing: root streams of
    # the same seed are bit-identical
    assert np.random.default_rng(0).random() == \
        np.random.default_rng(0).random()


def test_rng_stream_is_reproducible():
    a = RNG.stream(3, RNG.KIND_SAMPLING, 7).integers(0, 1 << 30, 4)
    b = RNG.stream(3, RNG.KIND_SAMPLING, 7).integers(0, 1 << 30, 4)
    assert np.array_equal(a, b)


# --- contracts --------------------------------------------------------------

def _hlo(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


def test_donation_contract_fails_on_undonated_step():
    x = jnp.zeros((64,), jnp.float32)
    bad = contracts.check_donation_text(_hlo(lambda v: v + 1, x), "bad")
    assert not bad.ok and "donate_argnums had no effect" in bad.detail


def test_donation_contract_passes_on_donated_step():
    x = jnp.zeros((64,), jnp.float32)
    good = contracts.check_donation_text(
        _hlo(lambda v: v + 1, x, donate_argnums=(0,)), "good")
    assert good.ok


def test_no_f64_contract_flags_wide_dtypes():
    ok = contracts.check_no_f64(
        jax.make_jaxpr(lambda v: v * 2)(jnp.ones((4,), jnp.float32)), "ok")
    assert ok.ok
    with jax.enable_x64(True):
        wide = jax.make_jaxpr(lambda v: v * 2)(np.ones((4,), np.float64))
    bad = contracts.check_no_f64(wide, "bad")
    assert not bad.ok and "float64" in bad.detail


def test_tier_shape_count_contract():
    ok = contracts.check_tier_shapes(
        {"compiled_tier_shapes": 4, "shape_lattice_bound": 32})
    assert ok.ok
    bad = contracts.check_tier_shapes(
        {"compiled_tier_shapes": 33, "shape_lattice_bound": 32})
    assert not bad.ok


@pytest.mark.slow
def test_round_engine_contracts_pass_end_to_end():
    reports = contracts.verify_round_engine(ragged=True)
    assert reports and all(r.ok for r in reports), \
        "\n".join(str(r) for r in reports)


# --- ownership audit --------------------------------------------------------

@pytest.mark.slow
def test_ownership_audit_clean_on_pipelined_ragged():
    violations, audit = audit_run(ragged=True)
    assert violations == [], violations
    objs = {t.obj for t in audit.touches}
    # the audit actually observed the full surface, not a no-op run
    assert {"store", "executor", "planner", "prefetch"} <= objs
    assert all(not t.is_main for t in audit.touches
               if t.obj == "prefetch")


@pytest.mark.slow
def test_ownership_audit_detects_rogue_store_touch():
    violations, audit = audit_run(ragged=True)
    assert violations == []
    rogue = threading.Thread(
        target=lambda: audit.last_store.prepare(
            np.array([0], np.int64), 99),
        name="rogue")
    rogue.start()
    rogue.join()
    flagged = audit.check(type("C", (), {"pipelined": True,
                                         "ragged": True})())
    assert any("rogue" in v and "store.prepare" in v for v in flagged), \
        flagged
