"""Named host spans of the Track-A round loop, on the profiler's clock.

``span(name, round=t)`` is ``jax.profiler.TraceAnnotation``:
while a ``jax.profiler`` trace runs, each span lands in the trace's
``/host:CPU`` plane, on its thread's line and on the same clock as the
device planes, with its keyword arguments as event stats. With no trace
running a span costs about a microsecond and records nothing; there is no
switch, buffer or exporter of this module's own.

Every span carries ``round=t``, which links round t's work on the main
thread to the worker's prefetch of round t; nesting on a thread gives the
parent. Only ``exec.dispatch`` carries counts, ``rows`` and ``valid``,
host integers the executor already holds, attached on exit with
``set_metadata`` (they feed the benchmark's ``chunk_fill``). A count
derived from a device array would sync only while tracing, so the traced
round would no longer describe the untraced one (REP006 flags it). The
round's wire and state-store counts live in ``Simulator.fault_log`` and
``ClientStateStore.telemetry()``, not on spans.

The spans, main thread unless marked worker:

* ``round`` — one round of ``Simulator.run``'s loop.
* ``round.wait_prefetch`` — the main thread waiting on the worker.
* ``round.setup`` — the round's host set-up before the executor: the
  staleness log, the learning rate, the plan arrays.
* ``round.plan`` — the masked path's ``planner.plan`` and ``advance``
  (inside ``round.setup``).
* ``exec.prepare`` — ``store.prepare``.
* ``exec.dispatch`` — the round's launches and their host inputs;
  ``rows`` (executed), ``valid`` (non-pad rows).
* ``exec.readback`` — the end-of-round bits and norms readback.
* ``wire.collect`` — uploads to the host, non-zeros found.
* ``wire.encode`` — attack payloads, encode, bit flips, send, drain.
* ``wire.decode`` — decode, CRC, retry, late sort.
* ``agg.host`` — norms, densify, aggregator update and finalize.
* ``round.account`` — the accounting sync and the Eq.-7 time model.
* ``round.eval`` — the eval boundary.
* ``prefetch`` — worker: the whole producer step for round t.
* ``prefetch.plan`` — worker: ``planner.plan``, fault draw, ``advance``.
* ``prefetch.gather`` — worker: the tier- or cap-shaped batch gather.

How to trace a run and read its spans: DESIGN.md §13.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span  # noqa: F401

SPANS = ("round", "round.wait_prefetch", "round.setup", "round.plan",
         "exec.prepare", "exec.dispatch", "exec.readback", "wire.collect",
         "wire.encode", "wire.decode", "agg.host", "round.account",
         "round.eval", "prefetch", "prefetch.plan", "prefetch.gather")
