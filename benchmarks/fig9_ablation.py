"""Paper Fig. 9 ablation: Caesar vs Caesar-BR (no deviation-aware compression)
vs Caesar-DC (no adaptive batch size)."""
from __future__ import annotations

from benchmarks import common as CM

VARIANTS = {
    "caesar": {},
    "caesar_br": {"use_deviation_compress": False},
    "caesar_dc": {"use_batch_opt": False},
}


def run(dataset="cifar10", log=lambda s: None):
    out = {}
    for name, kw in VARIANTS.items():
        cfg = CM.sim_config(dataset, "caesar", caesar_kw=kw)
        h, wall = CM.run_sim(cfg, log)
        out[name] = {"final_acc": h.accuracy[-1],
                     "traffic_gb": h.traffic_bits[-1] / 8e9,
                     "time_s": h.sim_time[-1]}
        CM.csv_row(f"fig9/{name}", wall / max(len(h.rounds), 1) * 1e6,
                   f"acc={h.accuracy[-1]:.3f};traffic_gb={h.traffic_bits[-1]/8e9:.3f};time_s={h.sim_time[-1]:.0f}")
    out["_summary"] = {
        "speedup_from_batch_opt": out["caesar_dc"]["time_s"] / out["caesar"]["time_s"],
        "traffic_saving_from_deviation_compress":
            1 - out["caesar"]["traffic_gb"] / out["caesar_br"]["traffic_gb"],
    }
    CM.save("fig9_ablation", out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(log=print)
