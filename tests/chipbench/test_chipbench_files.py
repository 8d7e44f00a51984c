"""Cells, configurations, traffic mixes and per-layer metrics are files
found by name: a new one is a new file, and no existing file changes."""
from __future__ import annotations

import json

import pytest

from chipbench import cell as CELL
from chipbench import kernels as K
from conftest import ROOT, write_json


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("wl", [w["name"] for w in bench()["workloads"]])
def test_every_workload_loads(wl):
    cell = CELL.load_cell(wl)
    assert cell.config["name"] == cell.name.split(".")[0]
    assert cell.traffic["sim"]["backend"] == "pallas"
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    assert cell.chips == 1


@pytest.mark.parametrize("m", [m["name"] for m in bench()["per_layer"]])
def test_every_metric_has_a_reader(m):
    assert callable(CELL.load_metric(m).read)


def test_config_files_match_benchmark():
    for c in bench()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / c["file"]).with_suffix(".py").exists()


def test_new_cell_and_metric_need_no_edit(bench_dir, tmp_path):
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    traffic = json.loads((bench_dir / "traffic" / "tiny.json").read_text())
    traffic["sim"]["participation"] = 0.5
    write_json(bench_dir / "traffic" / "tiny_half.json", traffic)
    write_json(bench_dir / "limits" / "cnn_har_tiny.tiny_half.json",
               {"loss_gap": 1})
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "cnn_har_tiny.tiny_half",
                           "config": "cnn_har_tiny", "traffic": "tiny_half",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                           "better": "higher", "source": "device_trace",
                           "layer": "test", "moves": "round_s",
                           "workloads": ["cnn_har_tiny.tiny_half"]})
    write_json(tmp_path / "BENCHMARK.json", b)
    cell = CELL.load_cell("cnn_har_tiny.tiny_half", root=tmp_path,
                          bench_dir=bench_dir)
    assert cell.traffic["sim"]["participation"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced"]
    reader = CELL.load_metric("rounds_traced", bench_dir)
    assert reader.read({"rounds": 5}) == 5.0
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_unknown_workload_is_refused():
    with pytest.raises(CELL.CellError):
        CELL.load_cell("no_such.cell")


def test_peaks_by_device_kind():
    pk = K.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        K.peaks("TPU v9 imaginary")
