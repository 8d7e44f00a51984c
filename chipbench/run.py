#!/usr/bin/env python3
"""Benchmark of the Caesar round on a TPU: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is a workload of ``BENCHMARK.json``: a model configuration under a
traffic mix. The run builds the system's ``Simulator`` from the cell's
files and the seed, drives it through the check's rounds and a warm-up of
every shape the window uses (set-up, reported as ``setup_s``), then times
``Simulator.run()`` over whole rounds for about ``--seconds`` seconds
(``round_s``: the window's wall time over its rounds). Once the window has
closed and the program's device state is freed, the plain reference under
``chipbench/reference`` replays the check's rounds and decides
``correct``. ``--trace 1`` profiles the window's last whole eval period and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; earlier lines report
the executor's telemetry, the compiles inside the window, the rounds, the
injected faults and the data digests. The run refuses to start, and prints
no result, unless JAX finds a TPU with as many chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import cell as CELL  # noqa: E402


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_tpu(chips: int) -> dict:
    """The devices, or NoChip when they are not TPUs or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache(root: Path) -> None:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or, where
    that is not set, at ``<checkout>/.jax_cache``; every program is kept."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def info(key: str, value) -> None:
    print(json.dumps({"info": key, "value": value}, default=str), flush=True)


def measure(cell, seed: int, seconds: float, trace: bool, device: dict,
            patch=None, t_start=None) -> dict:
    """One run of ``cell``: the result line's object."""
    trace_dir = None
    if trace:
        trace_dir = cell.root / ".chipbench" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    prog = CELL.run_program(cell, seed, seconds, trace_dir=trace_dir,
                            patch=patch, t_start=t_start)
    info("warmup", prog["warmup"])
    info("rounds_in_window", prog["rounds"])
    info("compiles_in_window", {"count": prog["compiles_in_window"],
                                "seconds": prog["compile_s_in_window"],
                                "new_tier_shapes":
                                    prog["new_tier_shapes_in_window"],
                                "programs": prog["compiled_in_window"]})
    info("telemetry", prog["telemetry"])
    info("injected_faults", prog["faults"])
    info("data_digest", prog["data_digest"])
    refdata = CELL.reference_data(cell, seed)
    ref = CELL.reference_rounds(cell, seed, refdata,
                                prog_globals=prog["check"]["globals"])
    nums = CELL.all_readings(prog, refdata, ref)
    info("readings", nums)
    checks = CELL.check(cell, nums)
    dev = dict(device, memory_peak_bytes=prog["memory_peak_bytes"])
    if trace:
        from chipbench import trace as TR
        red = TR.reduce_dir(trace_dir)
        info("traced", dict(prog["traced"], device_window_s=red["window_s"]))
        n, r = prog["rounds"], prog["traced"]["rounds"]
        ctx = {"trace": red, "prog": prog, "cell": cell, "rounds": r,
               "device": dict(device, count_used=int(cell.chips)),
               "planned_samples": CELL.planned_samples(cell, seed, refdata,
                                                       n - r + 1, n),
               "forward_flops": CELL.forward_flops(cell, refdata)}
        metrics = {}
        for m in cell.per_layer:
            val = CELL.load_metric(m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = TR.breakdown(red)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] in prog:
                metrics[m["name"]] = {"value": prog[m["name"]],
                                      "unit": m["unit"]}
        breakdown = None
    out = {"correct": CELL.is_correct(checks),
           "attempted": prog["attempted"], "failed": prog["failed"],
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = CELL.load_cell(args.workload)
        CELL.program_path(ROOT)
        wl = next(w for w in cell.bench["workloads"]
                  if w["name"] == args.workload)
        device = require_tpu(int(wl["chips"]))
        use_compile_cache(cell.root)
    except (NoChip, CELL.CellError, ImportError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    out = measure(cell, args.seed, args.seconds, bool(args.trace), device,
                  t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
