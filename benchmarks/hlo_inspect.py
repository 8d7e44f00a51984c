import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
"""Dump the largest collectives (with source metadata) for one dry-run cell.

  PYTHONPATH=src python -m benchmarks.hlo_inspect --arch deepseek-v3-671b \
      --shape train_4k --layers 1 --moe-layers 1
"""
import argparse
import dataclasses
import re

import jax

import repro.configs as configs
from repro.launch import dryrun as DR
from repro.launch import mesh as mesh_lib

_TYPE_RE = DR._TYPE_RE


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--dense-layers", type=int, default=-1)
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    kw = dict(n_layers=args.layers, unroll=True)
    if cfg.family == "moe":
        kw["n_dense_layers"] = (args.dense_layers if args.dense_layers >= 0
                                else min(cfg.n_dense_layers, 1))
        kw["n_layers"] = kw["n_dense_layers"] + args.layers
    cfg = dataclasses.replace(cfg, **kw)
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    with jax.set_mesh(mesh):
        compiled = DR._lower_cell(cfg, args.shape, mesh,
                                  dp_only=args.dp_only).compile()
        hlo = compiled.as_text()

    rows = []
    for line in hlo.splitlines():
        ls = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", ls)
        if not m:
            continue
        rhs = m.group(1)
        for c in DR.COLLECTIVES:
            if f" {c}(" in " " + rhs or f" {c}-start(" in " " + rhs:
                types = _TYPE_RE.findall(rhs.split(c, 1)[0])
                nbytes = sum(DR._shape_bytes(t, d) for t, d in types)
                meta = re.search(r'op_name="([^"]+)"', rhs)
                rows.append((nbytes, c,
                             types[:3], meta.group(1)[:110] if meta else ""))
                break
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"total collective result bytes: {total/1e9:.2f} GB "
          f"({len(rows)} ops)")
    for nb, c, types, meta in rows[:args.top]:
        print(f"{nb/1e9:9.3f}GB {c:18s} {str(types):44s} {meta}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
