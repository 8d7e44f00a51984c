"""The harness end to end on a tiny HAR cell on the CPU: a sound run is
correct; the control (the reference with float8 convolution and matmul
inputs in the program's place) and runs with the timed path broken
underneath are not. And the harness
refuses to run where JAX finds no TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import cell as CELL
from chipbench import control as CT
from chipbench import run as RUN
from conftest import ROOT

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 17


def tiny(bench_dir, traffic="tiny"):
    return CELL.load_cell(f"cnn_har_tiny.{traffic}", root=bench_dir.parent,
                          bench_dir=bench_dir)


def state_unchanged(sim):
    """The round returns the global model it was given."""
    sim.executor._finalize = lambda g, up_sum, cnt: g


def half_cohort(sim):
    """Every other participant's upload left out, the mean over the rest."""
    ex = sim.executor
    fold, fin = ex._fold, ex._finalize
    kept = {"n": 0}

    def fold_half(acc, ups, pmask):
        pm = np.asarray(pmask) * (np.arange(len(pmask)) % 2 == 0)
        kept["n"] += int(pm.sum())
        return fold(acc, ups, pm.astype(np.float32))

    def fin_half(g, up_sum, cnt):
        n, kept["n"] = kept["n"], 0
        return fin(g, up_sum, np.float32(max(n, 1)))

    ex._fold, ex._finalize = fold_half, fin_half


def answer_altered(sim):
    """The first upload of every chunk arrives with its sign flipped."""
    ex = sim.executor
    fold = ex._fold

    def fold_flip(acc, ups, pmask):
        return fold(acc, ups.at[0].multiply(-1.0), pmask)

    ex._fold = fold_flip


@pytest.mark.parametrize("traffic", ["tiny", "tinywire"])
def test_sound_run_is_correct(bench_dir, traffic):
    out = RUN.measure(tiny(bench_dir, traffic), SEED, 0.5, False, CPU)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, half_cohort,
                                   answer_altered])
def test_broken_timed_path_is_not_correct(bench_dir, fault):
    out = RUN.measure(tiny(bench_dir), SEED, 0.5, False, CPU, patch=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("mode,fault", [("control", "none"),
                                        ("reference", "half_cohort"),
                                        ("reference", "no_down_bits")])
def test_stand_in_is_not_correct(bench_dir, mode, fault):
    """The control (the reference with float8 inputs), the reference with
    half of each cohort left out, and the reference that counts no
    download traffic, each in the program's place."""
    cell = tiny(bench_dir)
    refdata = CELL.reference_data(cell, SEED)
    ref = CELL.reference_rounds(cell, SEED, refdata)
    got = CT.stand_in(cell, SEED, refdata, ref, mode, fault)
    assert any(got[k] > cell.limits[k] for k in cell.limits), got


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "cnn_har.dense500", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout of only BENCHMARK.json and the benchmark's paths."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable] + bench["command"][1:] + [
            "--workload", "cnn_har.dense500", "--seed", "1", "--seconds",
            "1", "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_leaf_diffs_see_what_norm_gaps_miss():
    """A leaf turned by 90 degrees keeps its norm: its gap of norms reads
    0, its difference reads sqrt(2)."""
    r = np.array([3.0, 4.0, 1.0, 0.0], np.float32)
    p = np.array([-4.0, 3.0, 1.0, 0.0], np.float32)
    keep = np.array([True, True])
    assert CELL.leaf_gaps(p, r, [2, 2], keep) == pytest.approx([0.0, 0.0])
    assert CELL.leaf_diffs(p, r, [2, 2], keep) == pytest.approx(
        [2 ** 0.5, 0.0], abs=1e-6)
