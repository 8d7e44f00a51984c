"""Benchmark harness entrypoint — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Track-A simulations run live
(budgets scaled for the single-core CPU container); the roofline table is
read from experiments/roofline.csv (produced by ``python -m
benchmarks.roofline``, which needs a fresh interpreter with 512 forced host
devices and is therefore not invoked in-process here).

Env:
  BENCH_FULL=1   also run the heavy datasets (cifar10, speech) in every table
"""
from __future__ import annotations

import csv
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

FULL = os.environ.get("BENCH_FULL", "0") == "1"


def _roofline_rows() -> None:
    """Surface the roofline table (per dry-run cell) as CSV rows."""
    path = ROOT / "experiments" / "roofline.csv"
    if not path.exists():
        print("roofline/:,0,missing (run: PYTHONPATH=src python -m "
              "benchmarks.roofline)")
        return
    with open(path) as f:
        for row in csv.DictReader(f):
            if row["status"] != "ok":
                continue
            name = f"roofline/{row['arch']}/{row['shape']}"
            t_bound = max(float(row["t_compute_s"]), float(row["t_memory_s"]),
                          float(row["t_collective_s"]))
            derived = (f"dominant={row['dominant']};"
                       f"frac={float(row['roofline_fraction']):.3f};"
                       f"useful={float(row['useful_ratio']):.2f}")
            print(f"{name},{t_bound*1e6:.0f},{derived}")


def main() -> None:
    from benchmarks import (fig1_preliminary, fig7_waiting,
                            fig8_heterogeneity, fig9_ablation, fig10_scales,
                            table3_overall)
    t0 = time.time()
    print("name,us_per_call,derived")

    table3_datasets = ("har", "oppo_ts") + (("cifar10", "speech") if FULL
                                            else ())
    table3_overall.run(datasets=table3_datasets)
    fig1_preliminary.run(dataset="har" if not FULL else "cifar10")
    fig7_waiting.run(dataset="har")
    fig8_heterogeneity.run(dataset="har")
    fig9_ablation.run(dataset="har" if not FULL else "cifar10")
    fig10_scales.run(dataset="har")
    _roofline_rows()
    print(f"# total benchmark wall time: {time.time()-t0:.0f}s",
          file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
