"""Paper Fig. 7: average synchronous-barrier waiting time per scheme."""
from __future__ import annotations

import statistics

from benchmarks import common as CM

SCHEMES = ["fedavg", "flexcom", "prowd", "pyramidfl", "caesar"]


def run(dataset="har", log=lambda s: None):
    out = {}
    for scheme in SCHEMES:
        h, _ = CM.run_sim(CM.sim_config(dataset, scheme), log)
        # History.waiting is the running per-round mean — the last entry
        # already averages EVERY simulated round, not a 1-in-eval_every
        # subsample. The µs column is the median per-round wall after
        # round 1, which carries the jit compile and is reported apart.
        w = float(h.waiting[-1])
        out[scheme] = w
        walls = h.wall_per_round
        CM.csv_row(f"fig7/{scheme}",
                   statistics.median(walls[1:] or walls) * 1e6,
                   f"avg_wait_s={w:.2f};round1_s={walls[0]:.2f}")
    CM.save("fig7_waiting", out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(log=print)
