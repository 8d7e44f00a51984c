#!/usr/bin/env python3
"""Readings that set the check's limits, on the chip, at a cell's size.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3]

For each seed: the program's readings (its check rounds against the plain
reference, as a run of ``run.py`` compares them). For each control seed
also: the control's readings (the reference with float8 convolution and
matmul inputs, put in the program's place), the half-cohort fault's (the
reference with half of each round's participants left out of the mean)
and the traffic fault's (the reference with the download payloads left
out of the traffic count). One JSON line per reading; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import cell as CELL  # noqa: E402
from chipbench import run as RUN  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def program_check(cell, seed: int) -> dict:
    """The program's check rounds, as a run of ``run.py`` makes them."""
    from repro.fl.simulation import Simulator
    sim = Simulator(CELL.sim_config(cell, seed, CELL.CHECK_ROUNDS))
    tap = CELL.RoundTap(sim, CELL.CompileCounter())
    CELL._run(sim, CELL.CHECK_ROUNDS)
    out = {"check": tap.remove(), "data_digest": CELL._program_digest(sim)}
    del sim, tap
    gc.collect()
    return out


def stand_in(cell, seed: int, refdata, ref: dict, mode: str,
             fault: str) -> dict:
    """The check's numbers for a reference run (in another precision, or
    with a fault) put in the program's place."""
    other = CELL.reference_rounds(cell, seed, refdata, mode=mode,
                                  fault=fault)
    prog = {"check": {"globals": other["globals"], "bits": other["bits"],
                      "tiers": other["tiers"]}, "data_digest": refdata[4]}
    return CELL.all_readings(prog, refdata,
                             dict(ref, prog_losses=other["losses"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    cell = CELL.load_cell(args.workload)
    CELL.program_path(ROOT)
    device = RUN.require_tpu(1)
    RUN.use_compile_cache(cell.root)
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        refdata = CELL.reference_data(cell, seed)
        prog = program_check(cell, seed) if seed in args.seeds else None
        ref = CELL.reference_rounds(
            cell, seed, refdata,
            prog_globals=prog["check"]["globals"] if prog else None)
        rows = []
        if prog is not None:
            rows.append(("program", CELL.all_readings(prog, refdata, ref)))
        if seed in args.control_seeds:
            rows.append(("control", stand_in(cell, seed, refdata, ref,
                                             "control", "none")))
            for fault in ("half_cohort", "no_down_bits"):
                rows.append((fault, stand_in(cell, seed, refdata, ref,
                                             "reference", fault)))
        for what, r in rows:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "what": what, "readings": r,
                              "device": device["kind"],
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
