"""Tests of the chip benchmark's harness, on the CPU.

They import ``chipbench`` from the repository root and build small cells
in a temporary directory, with the same files a real cell has.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_TRAFFIC = {
    "name": "tiny",
    "sim": {"scheme": "caesar", "n_clients": 12, "participation": 0.25,
            "p_heterogeneity": 5.0, "eval_every": 2, "eval_samples": 64,
            "state_capacity": 0, "backend": "jnp", "ragged": True,
            "pipelined": True, "wire": "inproc", "aggregation": "mean",
            "buffer_dtype": "float32"},
    "caesar": {"tau": 2, "b_max": 8, "b_min": 1, "theta_d_max": 0.6,
               "theta_u_min": 0.1, "theta_u_max": 0.6, "lam": 0.5,
               "n_clusters": 8, "use_error_feedback": False,
               "use_batch_opt": True, "use_deviation_compress": True,
               "plan_scope": "participants"},
    "sgd": {"lr": 0.1, "decay": 0.993, "momentum": 0.0},
    "max_rounds": 6,
    "warmup_rounds": 3,
    "reference_chunk": 3,
}

# the wire cell's path at a tiny size: loopback wire, dropouts, sign-flip
# attackers, trimmed mean
TINY_WIRE_TRAFFIC = dict(
    TINY_TRAFFIC, name="tinywire",
    sim=dict(TINY_TRAFFIC["sim"], n_clients=16, participation=0.5,
             wire="loopback", aggregation="trimmed_mean", trim_frac=0.125),
    faults={"dropout_rate": 0.125, "byzantine_frac": 0.125,
            "attack": "sign_flip", "attack_scale": 10.0})

# limits for the tiny cell on the CPU, where the program's convolutions
# run in float32 like the reference's: the sound run reads rounding only
TINY_LIMITS = {"loss_gap": 1e-3, "update1_median_gap": 1e-3,
               "update1_median_diff": 1e-3, "change3_median_gap": 1e-3,
               "bits_gap": 1e-3}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture
def bench_dir(tmp_path):
    """A copy of chipbench's file tree plus two tiny HAR cells,
    ``cnn_har_tiny.tiny`` and ``cnn_har_tiny.tinywire``, and a
    BENCHMARK.json that names them."""
    src = ROOT / "chipbench"
    dst = tmp_path / "chipbench"
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((src / "configs" / "cnn_har.json").read_text())
    cfg.update(name="cnn_har_tiny", loss_samples=64)
    cfg["sim"] = dict(cfg["sim"], data_scale=0.05)
    write_json(dst / "configs" / "cnn_har_tiny.json", cfg)
    shutil.copy(src / "configs" / "cnn_har.py",
                dst / "configs" / "cnn_har_tiny.py")
    write_json(dst / "traffic" / "tiny.json", TINY_TRAFFIC)
    write_json(dst / "traffic" / "tinywire.json", TINY_WIRE_TRAFFIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for traffic in ("tiny", "tinywire"):
        write_json(dst / "limits" / f"cnn_har_tiny.{traffic}.json",
                   TINY_LIMITS)
        bench["workloads"].append(
            {"name": f"cnn_har_tiny.{traffic}", "config": "cnn_har_tiny",
             "traffic": traffic, "chips": 1, "why": "a test cell"})
    write_json(tmp_path / "BENCHMARK.json", bench)
    return dst
