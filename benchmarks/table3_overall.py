"""Paper Table 3 + Figs. 5/6: traffic/time-to-accuracy of 5 schemes × datasets.

Reports, per (dataset, scheme): traffic (GB) and simulated wall-clock (h) to
the highest accuracy reachable by all schemes, plus final accuracy.
"""
from __future__ import annotations

from benchmarks import common as CM

SCHEMES = ["fedavg", "flexcom", "prowd", "pyramidfl", "caesar"]


def run(datasets=("har", "cifar10"), log=lambda s: None):
    rows = []
    for ds in datasets:
        hists, walls = {}, {}
        for scheme in SCHEMES:
            h, wall = CM.run_sim(CM.sim_config(ds, scheme), log)
            hists[scheme], walls[scheme] = h, wall
        target = CM.highest_common_accuracy(hists)
        base = hists["fedavg"].to_target(target)
        result = {"dataset": ds, "target": target}
        for scheme in SCHEMES:
            hit = hists[scheme].to_target(target)
            t, gb, rnd = hit if hit else (float("nan"),) * 3
            result[scheme] = {
                "time_to_target_s": t, "traffic_to_target_gb": gb,
                "rounds": rnd, "final_acc": hists[scheme].accuracy[-1],
                "traffic_saving_vs_fedavg":
                    (1 - gb / base[1]) if (hit and base) else None}
            us = walls[scheme] / max(len(hists[scheme].rounds), 1) * 1e6
            CM.csv_row(
                f"table3/{ds}/{scheme}", us,
                f"traffic_gb={gb:.3f};time_s={t:.0f};acc={hists[scheme].accuracy[-1]:.3f}")
        rows.append(result)
    CM.save("table3_overall", rows)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(log=print)
