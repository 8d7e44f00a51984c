"""Paper Fig. 8: final accuracy vs data-heterogeneity level p ∈ {1,5,10}."""
from __future__ import annotations

from benchmarks import common as CM

SCHEMES = ["fedavg", "prowd", "caesar"]
LEVELS = [1.0, 5.0, 10.0]


def run(dataset="har", log=lambda s: None):
    out = {}
    for p in LEVELS:
        for scheme in SCHEMES:
            cfg = CM.sim_config(dataset, scheme, p_heterogeneity=p)
            h, wall = CM.run_sim(cfg, log)
            out[f"{scheme}@p{p:g}"] = h.accuracy[-1]
            CM.csv_row(f"fig8/{scheme}/p{p:g}",
                       wall / max(len(h.rounds), 1) * 1e6,
                       f"final_acc={h.accuracy[-1]:.3f}")
    # robustness: accuracy degradation from p=1 to p=10 per scheme
    deg = {s: out[f"{s}@p1"] - out[f"{s}@p10"] for s in SCHEMES}
    out["_degradation"] = deg
    CM.save("fig8_heterogeneity", out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(log=print)
