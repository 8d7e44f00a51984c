"""Paper Fig. 1 preliminary study: one-directional FIC/CAC compression
(GM-* = model download only, LG-* = gradient upload only) vs no compression.
"""
from __future__ import annotations

from benchmarks import common as CM

VARIANTS = {
    "no_compression": dict(scheme="fedavg"),
    "gm_fic": dict(scheme="fic", fic_down_only=True),
    "gm_cac": dict(scheme="cac", fic_down_only=True),
    "lg_fic": dict(scheme="fic", fic_up_only=True),
    "lg_cac": dict(scheme="cac", fic_up_only=True),
}


def run(dataset="cifar10", log=lambda s: None):
    hists = {}
    out = {}
    for name, kw in VARIANTS.items():
        cfg = CM.sim_config(dataset, **kw)
        h, wall = CM.run_sim(cfg, log)
        hists[name] = h
        us = wall / max(len(h.rounds), 1) * 1e6
        out[name] = {"final_acc": h.accuracy[-1],
                     "traffic_gb": h.traffic_bits[-1] / 8e9,
                     "time_s": h.sim_time[-1]}
        CM.csv_row(f"fig1/{name}", us,
                   f"acc={h.accuracy[-1]:.3f};traffic_gb={h.traffic_bits[-1]/8e9:.3f}")
    # the paper's observation: compression speeds rounds but costs accuracy
    base = hists["no_compression"]
    out["_summary"] = {
        "speedups": {k: base.sim_time[-1] / hists[k].sim_time[-1]
                     for k in VARIANTS if k != "no_compression"},
        "acc_drops": {k: base.accuracy[-1] - hists[k].accuracy[-1]
                      for k in VARIANTS if k != "no_compression"},
    }
    CM.save("fig1_preliminary", out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(log=print)
