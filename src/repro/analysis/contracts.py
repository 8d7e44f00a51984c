"""Jaxpr/HLO contract verifier over the REAL traced round steps.

Static rules (analysis/lint.py) read source; this layer asserts what the
compiler actually produced. It builds a tiny Simulator twice (masked and
ragged), wraps the executor's jitted entry points so the first call of
each captures its jaxpr and compiled HLO text, runs a few rounds, and
checks four contracts:

* **no-f64** — no float64/complex128 aval anywhere in the traced step
  (x64 is off, so an f64 leak silently downcasts — the bug class REP005
  guards statically; this catches what slips through dynamic dtypes).
* **donation** — ``donate_argnums`` actually produced
  ``input_output_alias`` entries in the compiled module. jax only warns
  when a donation is unusable, and the in-place pool scatter is the
  difference between O(rows) and O(capacity) per round.
* **shape-lattice** — the set of compiled tier shapes stays within
  ``shape_lattice_bound()`` AND every seen (chunk, τ, b) is a lattice
  point (chunk ∈ chunk rungs, τ/b ∈ tier rungs). fig10's smoke gate
  calls ``check_tier_shapes`` on the same telemetry.
* **no-callbacks** — no host callback / infeed primitive hides in the
  step (a stray ``debug_callback`` would serialize every round on the
  host exactly like a REP006 sync).

``verify_track_b()`` traces the Track B collective train step (smoke
arch) for the no-f64/no-callback contracts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.extend.core as jex_core
import numpy as np

_BAD_DTYPES = ("float64", "complex128")
_CALLBACK_PRIMS = ("callback", "outside_call", "infeed", "outfeed",
                   "host_local_array")


@dataclasses.dataclass(frozen=True)
class ContractReport:
    name: str
    ok: bool
    detail: str = ""

    def __str__(self) -> str:
        mark = "PASS" if self.ok else "FAIL"
        return f"[{mark}] {self.name}" + (f": {self.detail}"
                                          if self.detail else "")


# --- jaxpr walking ----------------------------------------------------------

def iter_eqns(jaxpr):
    """Every eqn in a (Closed)Jaxpr, recursing into sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def _subjaxprs(value):
    if isinstance(value, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _avals(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for v in jaxpr.invars + jaxpr.outvars + jaxpr.constvars:
        if hasattr(v, "aval"):
            yield v.aval
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval


# --- individual contracts ---------------------------------------------------

def check_no_f64(closed_jaxpr, label: str) -> ContractReport:
    bad = sorted({str(a.dtype) for a in _avals(closed_jaxpr)
                  if str(getattr(a, "dtype", "")) in _BAD_DTYPES})
    return ContractReport(
        f"no-f64[{label}]", not bad,
        f"wide dtypes traced into the step: {bad}" if bad else "")


def check_no_callbacks(closed_jaxpr, label: str) -> ContractReport:
    hits = sorted({eqn.primitive.name for eqn in iter_eqns(closed_jaxpr)
                   if any(p in eqn.primitive.name
                          for p in _CALLBACK_PRIMS)})
    return ContractReport(
        f"no-callbacks[{label}]", not hits,
        f"host-callback primitives in the step: {hits}" if hits else "")


def check_donation_text(hlo_text: str, label: str,
                        expect_aliases: int = 1) -> ContractReport:
    """`input_output_alias` appears in compiled HLO iff donation aliased
    input→output buffers (verified against this jax/CPU build)."""
    ok = "input_output_alias" in hlo_text
    n = hlo_text.count("may-alias") + hlo_text.count("must-alias")
    if ok and n < expect_aliases:
        return ContractReport(
            f"donation[{label}]", False,
            f"only {n} aliased buffers (expected >= {expect_aliases}) — "
            "a donated operand lost its aliasing")
    return ContractReport(
        f"donation[{label}]", ok,
        "" if ok else "no input_output_alias in the compiled module — "
        "donate_argnums had no effect (pool copies every round)")


def check_tier_shapes(telemetry: dict,
                      label: str = "ragged") -> ContractReport:
    """Count bound from executor telemetry (fig10's smoke gate calls this
    with the per-point telemetry dict)."""
    seen = telemetry["compiled_tier_shapes"]
    bound = telemetry["shape_lattice_bound"]
    ok = seen <= bound
    return ContractReport(
        f"shape-lattice-count[{label}]", ok,
        f"{seen} compiled tier shapes vs lattice bound {bound}"
        + ("" if ok else " — jit cache is NOT bounded by the tier lattice"))


def check_tier_lattice_membership(executor,
                                  label: str = "ragged") -> ContractReport:
    from repro.core import batchsize as BS
    chunk_rungs = set(executor.chunk_rungs())
    b_rungs = set(np.asarray(
        BS.tier_rungs(executor.b_min, executor.b_cap)).tolist())
    t_rungs = set(np.asarray(
        BS.tier_rungs(1, executor.tau_cap)).tolist())
    off = sorted(s for s in executor._shapes_seen
                 if s[0] not in chunk_rungs or s[1] not in t_rungs
                 or s[2] not in b_rungs)
    return ContractReport(
        f"shape-lattice-member[{label}]", not off,
        f"off-lattice compiled shapes (chunk, tau, b): {off}" if off else
        f"{len(executor._shapes_seen)} shapes all on the lattice")


# --- capture + end-to-end verification --------------------------------------

class _Capture:
    """Wraps a jitted callable; first call records jaxpr + compiled HLO."""

    def __init__(self, jitted: Callable):
        self.jitted = jitted
        self.jaxpr = None
        self.hlo: Optional[str] = None

    def __call__(self, *args, **kwargs):
        if self.jaxpr is None:
            self.jaxpr = jax.make_jaxpr(self.jitted)(*args, **kwargs)
            self.hlo = self.jitted.lower(*args, **kwargs).compile().as_text()
        return self.jitted(*args, **kwargs)


def _tiny_cfg(**overrides):
    from repro.core.caesar import CaesarConfig
    from repro.fl.simulation import SimConfig
    base = dict(dataset="oppo_ts", rounds=3, n_clients=12, data_scale=0.01,
                eval_every=3, participation=0.5, seed=0,
                dataset_kwargs={"n_features": 64},
                # EF on so all three donated buffers are non-empty (an
                # empty EF pool would legitimately lose its alias)
                caesar=CaesarConfig(tau=2, b_max=8, use_error_feedback=True),
                pipelined=False)
    base.update(overrides)
    return SimConfig(**base)


def verify_round_engine(ragged: bool, **overrides) -> list:
    """Build a tiny sim, trace+run the (masked|ragged) engine, check all
    contracts against the captured artifacts."""
    from repro.fl.simulation import Simulator
    label = "ragged" if ragged else "masked"
    sim = Simulator(_tiny_cfg(ragged=ragged, **overrides))
    ex = sim.executor
    caps = {}
    if ragged:
        # unsharded ragged rounds run the deferred kernel + the
        # association-fixed fold (shared with the wire replay) — the
        # fused tier_chunk only exists on the sharded path
        caps["tier_chunk_defer"] = ex._tier_chunk_defer = \
            _Capture(ex._tier_chunk_defer)
        caps["fold"] = ex._fold = _Capture(ex._fold)
        caps["finalize"] = ex._finalize = _Capture(ex._finalize)
    else:
        caps["round_step"] = ex._round_step = _Capture(ex._round_step)
    sim.run()

    # donated-buffer counts: pool+EF+accumulator for the masked round
    # step, pool+EF for the deferred chunk kernel, the carry for the
    # fold/finalizer
    expect_aliases = {"round_step": 3, "tier_chunk_defer": 2,
                      "fold": 1, "finalize": 1}
    reports = []
    for name, cap in caps.items():
        if cap.jaxpr is None:
            reports.append(ContractReport(
                f"traced[{label}/{name}]", False, "never called"))
            continue
        reports.append(check_no_f64(cap.jaxpr, f"{label}/{name}"))
        reports.append(check_no_callbacks(cap.jaxpr, f"{label}/{name}"))
        reports.append(check_donation_text(
            cap.hlo, f"{label}/{name}",
            expect_aliases=expect_aliases[name]))
    if ragged:
        reports.append(check_tier_shapes(ex.telemetry(), label))
        reports.append(check_tier_lattice_membership(ex, label))
    return reports


def verify_wire_engine(**overrides) -> list:
    """Trace the wire-boundary engine's deferred chunk step (DESIGN.md
    §11) through a tiny loopback run with faults + robust aggregation —
    the step donates 2 buffers (pool, EF) and must obey the same no-f64 /
    no-callback contracts as the fused path it mirrors."""
    from repro.fl import faults as F
    from repro.fl.simulation import Simulator
    sim = Simulator(_tiny_cfg(
        ragged=True, wire="loopback",
        faults=F.FaultConfig(dropout_rate=0.2, byzantine_frac=0.2),
        aggregation="trimmed_mean", **overrides))
    ex = sim.executor
    cap = ex._tier_chunk_defer = _Capture(ex._tier_chunk_defer)
    sim.run()
    if cap.jaxpr is None:
        return [ContractReport("traced[wire/tier_chunk_defer]", False,
                               "never called")]
    return [check_no_f64(cap.jaxpr, "wire/tier_chunk_defer"),
            check_no_callbacks(cap.jaxpr, "wire/tier_chunk_defer"),
            check_donation_text(cap.hlo, "wire/tier_chunk_defer",
                                expect_aliases=2)]


def verify_track_b() -> list:
    """Trace the Track B collective train step (smoke arch, 1×1 mesh)."""
    import dataclasses as dc

    import repro.configs as configs
    from repro.fl import distributed as D
    from repro.models import model as M

    cfg = dc.replace(configs.get("qwen1p5_4b").smoke(), local_iters=2)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    dcfg = D.DistConfig(theta_d=0.3, theta_u=0.4, local_lr=1e-2,
                        use_error_feedback=True)
    state = D.init_state(params, dcfg, mesh=None)
    step = D.make_train_step(cfg, dcfg, mesh=None)
    jaxpr = jax.make_jaxpr(step)(state, batch)
    return [check_no_f64(jaxpr, "track_b"),
            check_no_callbacks(jaxpr, "track_b")]


def run_contracts(track_b: bool = True) -> list:
    reports = verify_round_engine(ragged=False)
    reports += verify_round_engine(ragged=True)
    reports += verify_wire_engine()
    if track_b:
        reports += verify_track_b()
    return reports
