import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
"""Roofline analysis (EXPERIMENTS.md §Roofline): three terms per (arch×shape).

Methodology
-----------
``compiled.cost_analysis()`` is per-device and counts each scan (while-loop)
body ONCE, so full-depth records under-count layer costs. We therefore fit
per-layer costs from *probe* compiles: same batch/seq/width/mesh, reduced
layer counts, scans unrolled (every layer statically present):

    dense/ssm/encoder/vlm:  cost(L)       = a + b·L            (probes L=1,2)
    moe (deepseek):         cost(nd,nm)   = a + bd·nd + bm·nm  (3 probes)
    moe (llama4, nd=0):     cost(nm)      = a + bm·nm          (2 probes)
    hybrid (zamba2):        cost(Lm,ns)   = a + b·Lm + c·ns    (3 probes)

and extrapolate to the full depth. Collective wire-bytes use ring-algorithm
factors on the HLO result bytes: AR 2(n−1)/n, AG/RS/A2A (n−1)/n, permute 1.

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link ICI.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--arch X] [--shape Y]
Writes experiments/roofline.csv and experiments/roofline.md.
"""
import argparse
import dataclasses
import json
import math
import time
import traceback
from pathlib import Path

import jax

import repro.configs as configs
from repro.launch import dryrun as DR
from repro.launch import mesh as mesh_lib
from repro.launch import specs as S
from repro.models import model as M

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9
N_CHIPS = 256

ROOT = Path(__file__).resolve().parent.parent / "experiments"
PROBE_DIR = ROOT / "dryrun" / "probes"

WIRE_FACTOR = {
    "all-reduce": lambda n: 2 * (n - 1) / n if n > 1 else 0.0,
    "all-gather": lambda n: (n - 1) / n if n > 1 else 0.0,
    "reduce-scatter": lambda n: (n - 1) if n > 1 else 0.0,  # result = shard
    "all-to-all": lambda n: (n - 1) / n if n > 1 else 0.0,
    "collective-permute": lambda n: 1.0,
}


def wire_bytes(census: dict) -> float:
    total = 0.0
    for op, rec in census.items():
        f = WIRE_FACTOR[op]
        for o in rec.get("ops", []):
            n = o["group"] or 16
            total += o["bytes"] * f(n)
        # ops list may be truncated at 200; scale by count ratio
        listed = len(rec.get("ops", []))
        if listed and rec["count"] > listed:
            total *= rec["count"] / listed
    return total


# ---------------------------------------------------------------------------
# Probes
# ---------------------------------------------------------------------------

def _probe_cfg(cfg, **kw):
    return dataclasses.replace(cfg, unroll=True, **kw)


def probe_variants(cfg):
    """[(tag, cfg, coeff_vector)] + solve() for the full-depth extrapolation."""
    fam = cfg.family
    if fam == "moe" and cfg.n_dense_layers > 0:
        vs = [("nd1_nm1", _probe_cfg(cfg, n_layers=2, n_dense_layers=1)),
              ("nd2_nm1", _probe_cfg(cfg, n_layers=3, n_dense_layers=2)),
              ("nd1_nm2", _probe_cfg(cfg, n_layers=3, n_dense_layers=1))]

        def solve(c):
            bd = max(0.0, c["nd2_nm1"] - c["nd1_nm1"])
            bm = max(0.0, c["nd1_nm2"] - c["nd1_nm1"])
            a = max(0.0, c["nd1_nm1"] - bd - bm)
            return (a + bd * cfg.n_dense_layers
                    + bm * (cfg.n_layers - cfg.n_dense_layers))
        return vs, solve
    if fam == "moe":
        vs = [("nm1", _probe_cfg(cfg, n_layers=1)),
              ("nm2", _probe_cfg(cfg, n_layers=2))]

        def solve(c):
            b = max(0.0, c["nm2"] - c["nm1"])
            return max(0.0, c["nm1"] - b) + b * cfg.n_layers
        return vs, solve
    if fam == "hybrid":
        vs = [("l1_s1", _probe_cfg(cfg, n_layers=1)),
              ("l2_s1", _probe_cfg(cfg, n_layers=2)),
              ("l2_s2", _probe_cfg(cfg, n_layers=2, attn_every=1))]

        def solve(c):
            b = max(0.0, c["l2_s1"] - c["l1_s1"])
            cs = max(0.0, c["l2_s2"] - c["l2_s1"])
            a = max(0.0, c["l1_s1"] - b - cs)
            n_s = math.ceil(cfg.n_layers / cfg.attn_every)
            return a + b * cfg.n_layers + cs * n_s
        return vs, solve
    vs = [("l1", _probe_cfg(cfg, n_layers=1)),
          ("l2", _probe_cfg(cfg, n_layers=2)),
          ("l4", _probe_cfg(cfg, n_layers=4))]

    def solve(c):
        # robust fit: XLA sometimes picks different layouts at L=1, making
        # 2-point fits non-monotone; prefer the (L=2, L=4) slope, clamp ≥ 0.
        if "l4" in c:
            b = max(0.0, (c["l4"] - c["l2"]) / 2.0)
            a = max(0.0, c["l2"] - 2 * b)
        else:
            b = max(0.0, c["l2"] - c["l1"])
            a = max(0.0, c["l1"] - b)
        return a + b * cfg.n_layers
    return vs, solve


def probe_cell(arch: str, shape: str, force=False, dp_only=False,
               variant_tag="") -> dict | None:
    cfg = configs.get(arch)
    ok, _ = S.cell_supported(cfg, shape)
    if not ok:
        return None
    PROBE_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{variant_tag}" if variant_tag else ""
    fname = PROBE_DIR / f"{cfg.name}__{shape}{suffix}.json"
    if fname.exists() and not force:
        return json.loads(fname.read_text())
    mesh = mesh_lib.make_production_mesh(multi_pod=False)
    variants, _ = probe_variants(cfg)
    out = {}
    for tag, vcfg in variants:
        t0 = time.time()
        try:
            with jax.set_mesh(mesh):
                lowered = DR._lower_cell(vcfg, shape, mesh, dp_only=dp_only)
                compiled = lowered.compile()
                cost = compiled.cost_analysis()
                census = DR.collective_census(compiled.as_text())
            out[tag] = {"flops": float(cost.get("flops", 0.0)),
                        "bytes": float(cost.get("bytes accessed", 0.0)),
                        "wire": wire_bytes(census),
                        "compile_s": round(time.time() - t0, 1)}
            print(f"  probe {cfg.name}/{shape}/{tag}: "
                  f"flops={out[tag]['flops']:.3e} ({out[tag]['compile_s']}s)",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            out[tag] = {"error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:]}
            print(f"  probe {cfg.name}/{shape}/{tag}: ERROR {e}", flush=True)
    fname.write_text(json.dumps(out, indent=1))
    return out


# ---------------------------------------------------------------------------
# Model FLOPs (analytic 6·N·D / 2·N·D)
# ---------------------------------------------------------------------------

def param_counts(cfg) -> tuple[int, int]:
    """(total, active) parameter counts, exact from abstract shapes."""
    ab = M.init_abstract(cfg)
    total = sum(int(l.size) for l in jax.tree.leaves(ab))
    active = total
    if cfg.family == "moe":
        moe = ab["moe_layers"]["ffn"]
        routed = sum(int(moe[k].size) for k in ("w_gate", "w_up", "w_down"))
        active = total - routed + routed * cfg.moe_top_k / cfg.n_experts
    return total, int(active)


def model_flops(cfg, shape: str) -> float:
    info = S.SHAPES[shape]
    _, active = param_counts(cfg)
    if info["kind"] == "train":
        tokens = info["batch"] * info["seq"]
        return 6.0 * active * tokens / N_CHIPS
    if info["kind"] == "prefill":
        tokens = info["batch"] * info["seq"]
        return 2.0 * active * tokens / N_CHIPS
    return 2.0 * active * info["batch"] / N_CHIPS   # decode: 1 token/seq


# ---------------------------------------------------------------------------
# Roofline assembly
# ---------------------------------------------------------------------------

def analyse(arch: str, shape: str, tag="baseline", force=False,
            dp_only=False, variant_tag="") -> dict:
    cfg = configs.get(arch)
    ok, why = S.cell_supported(cfg, shape)
    row = {"arch": cfg.name, "shape": shape}
    if not ok:
        row.update(status="skipped", why=why)
        return row
    probes = probe_cell(arch, shape, force=force, dp_only=dp_only,
                        variant_tag=variant_tag)
    _, solve = probe_variants(cfg)
    if any("error" in v for v in probes.values()):
        row.update(status="probe_error",
                   why="; ".join(v.get("error", "") for v in probes.values()))
        return row
    flops = solve({k: v["flops"] for k, v in probes.items()})
    hbytes = solve({k: v["bytes"] for k, v in probes.items()})
    wire = max(0.0, solve({k: v["wire"] for k, v in probes.items()}))

    t_comp = flops / PEAK_FLOPS
    t_mem = hbytes / HBM_BW
    t_coll = wire / LINK_BW
    dominant = max((t_comp, "compute"), (t_mem, "memory"),
                   (t_coll, "collective"))[1]
    mf = model_flops(cfg, shape)
    bound = max(t_comp, t_mem, t_coll)
    # roofline fraction: useful model FLOP-time over the binding resource time
    frac = (mf / PEAK_FLOPS) / bound if bound > 0 else 0.0

    # memory per chip from the full-depth dry-run record (argument+temp)
    full = ROOT / "dryrun" / f"{cfg.name}__{shape}__pod16x16__{tag}.json"
    mem_gb = None
    if full.exists():
        rec = json.loads(full.read_text())
        if rec.get("status") == "ok":
            m = rec["memory"]
            mem_gb = (m["argument_size_in_bytes"] + m["temp_size_in_bytes"]
                      + m["output_size_in_bytes"]) / 1e9
    row.update(status="ok", flops=flops, hbm_bytes=hbytes, wire_bytes=wire,
               t_compute_s=t_comp, t_memory_s=t_mem, t_collective_s=t_coll,
               dominant=dominant, model_flops=mf,
               useful_ratio=mf / flops if flops else 0.0,
               roofline_fraction=frac, mem_gb_per_chip=mem_gb)
    return row


SUGGESTIONS = {
    "compute": "raise MXU utilization: fuse small ops, widen matmul tiles, "
               "drop causal-masked wasted attention FLOPs",
    "memory": "cut HBM passes: fuse compression ops (Pallas), avoid f32 "
              "up-casts, rematerialize less on the serving path",
    "collective": "shrink payloads: bf16/quantized collectives, "
                  "reduce-scatter instead of all-reduce, overlap with compute",
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--variant-tag", default="",
                    help="suffix for probe cache + output csv (hillclimb runs)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = configs.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(S.SHAPES) if args.shape == "all" else [args.shape]

    rows = []
    for arch in archs:
        for shape in shapes:
            print(f"[roofline] {arch} × {shape}", flush=True)
            rows.append(analyse(arch, shape, tag=args.tag, force=args.force,
                                dp_only=args.dp_only,
                                variant_tag=args.variant_tag))

    suffix = f"_{args.variant_tag}" if args.variant_tag else ""
    csv_path = ROOT / f"roofline{suffix}.csv"
    with open(csv_path, "w") as f:
        cols = ["arch", "shape", "status", "t_compute_s", "t_memory_s",
                "t_collective_s", "dominant", "flops", "hbm_bytes",
                "wire_bytes", "model_flops", "useful_ratio",
                "roofline_fraction", "mem_gb_per_chip", "why"]
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r.get(c, "")) for c in cols) + "\n")
    print(f"wrote {csv_path}")

    md = ["| arch | shape | t_comp (ms) | t_mem (ms) | t_coll (ms) | dominant "
          "| useful | roofline frac | next lever |",
          "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("status") != "ok":
            md.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                      f"skip: {r.get('why','')[:60]} | | | |")
            continue
        md.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']*1e3:.2f} | "
            f"{r['t_memory_s']*1e3:.2f} | {r['t_collective_s']*1e3:.2f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2f} | "
            f"{SUGGESTIONS[r['dominant']][:48]} |")
    (ROOT / f"roofline{suffix}.md").write_text("\n".join(md) + "\n")
    print((ROOT / f"roofline{suffix}.md").as_posix())


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
