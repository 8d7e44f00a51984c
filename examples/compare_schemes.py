"""Reproduce the paper's headline comparison on one dataset: all five schemes,
traffic/time to a common target accuracy (Table 3 style, CPU budget).

  PYTHONPATH=src python examples/compare_schemes.py --dataset har
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # benchmarks/

from benchmarks import table3_overall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="har",
                    choices=["har", "cifar10", "speech", "oppo_ts"])
    args = ap.parse_args()
    rows = table3_overall.run(datasets=(args.dataset,), log=print)
    r = rows[0]
    print(f"\ntarget acc = {r['target']:.3f}")
    for scheme in ("fedavg", "flexcom", "prowd", "pyramidfl", "caesar"):
        d = r[scheme]
        print(f"{scheme:10s} traffic={d['traffic_to_target_gb']:.3f}GB "
              f"time={d['time_to_target_s']:.0f}s acc={d['final_acc']:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
