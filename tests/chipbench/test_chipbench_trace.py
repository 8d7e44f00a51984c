"""The trace reduction on a synthetic trace: the window as the span of the
device's work, busy union, idle share, per-program and per-kernel sums,
kernel bytes, gap labels."""
from __future__ import annotations

import pytest

from chipbench import trace as TR


def ev(name, start, dur, **stats):
    return TR.Event(name, start, dur, stats)


HLO = ("%magnitude_histogram.3 = f32[2,256,128]{2,1,0} custom-call("
       "f32[2,1024,128]{2,1,0} %x, f32[1,1]{1,0} %s)")


@pytest.fixture
def raw():
    ops = [ev("fusion.1", 0.10, 0.20), ev("fusion.2", 0.25, 0.10),
           ev("custom-call.7", 0.50, 0.05, long_name=HLO),
           ev("convolution.3", 0.80, 0.10)]
    modules = [ev("jit_tier_chunk_defer(12)", 0.10, 0.25),
               ev("jit_weighted_row_fold(3)", 0.50, 0.05),
               ev("jit_finalize(4)", 0.80, 0.10)]
    host = [ev("PjitFunction(tier_chunk_defer)", 0.36, 0.13),
            ev("TransferToDevice", 0.56, 0.22),
            ev("$simulation.py:600 run", 0.0, 1.0)]
    return {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops}},
            "host": host}


def test_union_merges_and_clips():
    assert TR.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0, 10) == \
        [[0, 3], [5, 6], [9, 10]]


def test_busy_and_idle(raw):
    red = TR.reduce(raw)
    # programs span [0.10, 0.90]; ops cover [0.10, 0.35] ∪ [0.50, 0.55]
    # ∪ [0.80, 0.90]
    assert red["window_s"] == pytest.approx(0.80)
    assert red["busy_s"] == pytest.approx(0.40)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.50)


def test_programs_and_kernels(raw):
    red = TR.reduce(raw)
    assert red["programs"]["tier_chunk_defer"] == {"calls": 1,
                                                   "seconds": 0.25}
    assert set(red["programs"]) == {"tier_chunk_defer", "weighted_row_fold",
                                    "finalize"}
    k = red["kernels"]["magnitude_histogram"]
    assert k["calls"] == 1 and k["seconds"] == pytest.approx(0.05)
    assert k["bytes"] == 4 * (2 * 256 * 128 + 2 * 1024 * 128 + 1)


@pytest.mark.parametrize("event,label", [
    ("Transpose", "transfer"), ("tpu::System::TransferToDevice", "transfer"),
    ("CommonPjRtLoadedExecutable::Execute", "dispatch"),
    ("PjitFunction(finalize)", "dispatch finalize"),
    ("$numeric.py:10 take", "python"), ("MemoryAllocation",
                                         "MemoryAllocation")])
def test_gap_label_names(event, label):
    assert TR.label_gap(0.0, 1.0, [ev(event, 0.0, 1.0)]) == label


def test_gap_labels(raw):
    red = TR.reduce(raw)
    labels = [lab for lab, _ in red["gaps"]]
    # [.35, .50) dispatch; [.55, .80) transfer
    assert labels == ["dispatch tier_chunk_defer", "transfer"]
    gaps = dict(TR.breakdown(red)["idle_gaps"])
    assert gaps["transfer"] == pytest.approx(0.25)


def test_devices_averaged(raw):
    raw["devices"]["/device:TPU:1"] = {"modules": [],
                                       "ops": [ev("fusion.9", 0.0, 1.0)]}
    red = TR.reduce(raw)
    assert red["n_devices"] == 2
    assert red["busy_s"] == pytest.approx(0.70)


def test_window_excludes_later_events(raw):
    """Host events before the first program and after the last (the
    profiler starting, the trace being written) lie outside the window."""
    raw["host"] += [ev("ProfilerStart", -3.0, 2.0),
                    ev("ExportToXSpace", 1.0, 8.0)]
    red = TR.reduce(raw)
    assert red["window_s"] == pytest.approx(0.80)
    assert sum(s for _, s in red["gaps"]) == pytest.approx(0.40)


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce({"devices": {}, "host": []})


def test_idle_device_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce({"devices": {"/device:TPU:0": {"modules": [], "ops": []}},
                   "host": []})


@pytest.mark.parametrize("module,name", [
    ("jit_tier_chunk_defer(12)", "tier_chunk_defer"),
    ("jit__power(-5848927215300589880)", "_power"),
    ("jit_finalize", "finalize"), ("jit_hist.3", "hist"),
    ("evaluate(7)", "evaluate")])
def test_program_name(module, name):
    assert TR.program_name(module) == name


def test_hlo_bytes_counts_every_shape():
    assert TR.hlo_bytes("(f32[4,8], s8[4,8]) custom-call(bf16[2] %a)") == \
        4 * 32 + 32 + 2 * 2
    assert TR.hlo_bytes("") == 0
