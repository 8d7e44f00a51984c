"""The program's spans in a trace (``chipbench/spans.py``), on synthetic
host lines: clipping to the device window, self time, count sums, device
idle time given to the innermost span of the main line and never to the
worker's, and each reader that reads them."""
from __future__ import annotations

import threading

import pytest

from chipbench import cell as CELL
from chipbench import spans as S
from chipbench import trace as TR


def ev(name, start, end, **stats):
    return TR.Event(name, start, end - start, stats)


@pytest.fixture
def raw():
    # device window [0.05, 1.05]; idle [0.35, 0.72] and [0.80, 0.95]
    ops = [ev("fusion.1", 0.05, 0.35), ev("fusion.2", 0.72, 0.80),
           ev("fusion.3", 0.95, 1.05)]
    return {"devices": {"/device:TPU:0": {"modules": [], "ops": ops}},
            "host": []}


@pytest.fixture
def lines():
    main = [ev("round", 0.00, 1.00, round=1),
            ev("round.wait_prefetch", 0.00, 0.05, round=1),
            ev("exec.dispatch", 0.05, 0.30, round=1, rows=8, valid=6),
            ev("exec.readback", 0.30, 0.40, round=1),
            ev("wire.encode", 0.40, 0.70, round=1),
            ev("agg.host", 0.70, 0.90, round=1),
            ev("round", 1.00, 1.20, round=2),
            ev("exec.dispatch", 1.00, 1.10, round=2, rows=4, valid=4)]
    # the worker's spans cover the first gap too, and more tightly
    worker = [ev("prefetch", 0.35, 0.70, round=2),
              ev("prefetch.gather", 0.40, 0.50, round=2)]
    return [worker, main]


@pytest.fixture
def summary(raw, lines):
    return S.summarize(raw, lines)


def test_spans_clipped_to_the_device_window(summary):
    sp = summary["spans"]
    assert summary["window_s"] == pytest.approx(1.0)
    # the wait lies before the device's first program: outside
    assert "round.wait_prefetch" not in sp
    assert sp["round"]["calls"] == 2
    assert sp["round"]["seconds"] == pytest.approx(0.95 + 0.05)
    assert sp["exec.dispatch"]["seconds"] == pytest.approx(0.25 + 0.05)


def test_self_time_leaves_out_nested_spans(summary):
    sp = summary["spans"]
    # round 1 in the window [0.05, 1.00]: children cover [0.05, 0.90]
    assert sp["round"]["self_s"] == pytest.approx(0.10)
    assert sp["prefetch"]["self_s"] == pytest.approx(0.35 - 0.10)
    assert sp["agg.host"]["self_s"] == pytest.approx(0.20)


def test_counts_are_summed(summary):
    sp = summary["spans"]
    assert sp["exec.dispatch"]["counts"] == {"rows": 12, "valid": 10}
    # ``round`` is the span's identifier, not a count
    assert sp["round"]["counts"] == {}
    assert sp["prefetch.gather"]["counts"] == {}


def test_idle_goes_to_the_main_lines_innermost_span(summary):
    sp = summary["spans"]
    assert summary["main"]
    assert summary["idle_s"] == pytest.approx(0.37 + 0.15)
    assert sp["exec.readback"]["idle_s"] == pytest.approx(0.05)
    assert sp["wire.encode"]["idle_s"] == pytest.approx(0.30)
    assert sp["agg.host"]["idle_s"] == pytest.approx(0.02 + 0.10)
    # never to the worker's spans, nor to ``round`` itself
    assert sp["prefetch"]["idle_s"] == sp["prefetch.gather"]["idle_s"] == 0
    assert sp["round"]["idle_s"] == 0
    assert summary["idle_attributed_s"] == pytest.approx(0.47)


def test_no_program_spans_no_main_line(raw):
    out = S.summarize(raw, [])
    assert out["spans"] == {} and not out["main"]
    assert out["idle_s"] == pytest.approx(0.52)


# per-round readers divide by the traced rounds, ctx["rounds"] = 2
READINGS = {
    "prefetch_ms": 1e3 * 0.35 / 2,
    "prefetch_wait_ms": None,                   # outside the window
    "dispatch_host_ms": 1e3 * 0.30 / 2,         # self time
    "chunk_fill": 100.0 * 10 / 12,
    "wire_ms": 1e3 * 0.30 / 2,
    "agg_host_ms": 1e3 * 0.20 / 2,
    "idle_unattributed_share": 100.0 * 0.05 / 0.52,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_metric_readers(name, summary, monkeypatch):
    monkeypatch.setattr(S, "of", lambda ctx: summary)
    got = CELL.load_metric(name).read({"rounds": 2})
    want = READINGS[name]
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", sorted(READINGS))
def test_metric_readers_without_spans(name, raw, monkeypatch):
    """A program without spans: every reader reports nothing."""
    monkeypatch.setattr(S, "of", lambda ctx: S.summarize(raw, []))
    assert CELL.load_metric(name).read({"rounds": 2}) is None


def test_load_keeps_threads_apart(tmp_path):
    """A real CPU trace: the program's spans (events with a ``round``
    stat) per thread line, their stats as numbers; the runtime's own
    events left out, and no TPU plane."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def worker():
        with TraceAnnotation("prefetch", round=2):
            pass

    th = threading.Thread(target=worker)
    with TraceAnnotation("round", round=1) as sp:
        th.start()
        th.join()
        (jnp.ones(4) + 1).block_until_ready()
        sp.set_metadata(parts=3)
    jax.profiler.stop_trace()
    path = TR.find_xplane(tmp_path)
    devices, lines = S.load(path)
    got = sorted([(e.name, e.stats) for e in ln] for ln in lines)
    assert got == [[("prefetch", {"round": 2})],
                   [("round", {"round": 1, "parts": 3})]]
    assert devices == {"devices": {}}
    # no TPU in a CPU trace: the summary needs the device window
    with pytest.raises(ValueError):
        S.read(tmp_path)


def test_read_once_per_file(tmp_path, raw, lines, monkeypatch):
    f = tmp_path / "x" / "host.xplane.pb"
    f.parent.mkdir()
    f.write_bytes(b"0")
    calls = []
    monkeypatch.setattr(S, "load", lambda p: calls.append(p) or (raw, lines))
    S._read.cache_clear()
    first = S.read(tmp_path)
    assert S.read(tmp_path) is first and len(calls) == 1
    f.write_bytes(b"01")                 # a new trace at the same path
    assert S.read(tmp_path) is not first and len(calls) == 2
    S._read.cache_clear()
