"""Quickstart: train a CNN with Caesar's low-deviation compression (Track A).

Runs the faithful multi-client FL simulator on a synthetic HAR-shaped task
and prints the traffic/accuracy trajectory vs uncompressed FedAvg.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.core.caesar import CaesarConfig
from repro.fl.simulation import SimConfig, Simulator


def main():
    for scheme in ("caesar", "fedavg"):
        cfg = SimConfig(dataset="har", scheme=scheme, rounds=20,
                        n_clients=30, participation=0.2, data_scale=0.2,
                        eval_every=5,
                        caesar=CaesarConfig(tau=5, b_max=16))
        hist = Simulator(cfg).run(log=print)
        s = hist.summary()
        print(f"== {scheme}: acc={s['final_acc']:.3f} "
              f"traffic={s['total_traffic_gb']:.3f}GB "
              f"sim_time={s['total_time_s']:.0f}s\n")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
