"""End-to-end Track-B training driver (cohort-mode Caesar on a mesh).

Runs real steps on the available devices (CPU in this container: use the
local 1×1 mesh or a forced-device-count subprocess), with Caesar round
scheduling, checkpoint/restart, and failure-tolerant resume.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.checkpoint.manager import CheckpointManager
from repro.core import rng as RNG
from repro.core import staleness as ST
from repro.fl import distributed as D
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import model as M


def make_batch(rng, cfg, batch, seq):
    toks = rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32)
    out = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    if cfg.frontend == "audio":
        out = {"frames": jnp.asarray(
            rng.normal(size=(batch, seq, cfg.frontend_dim)).astype(np.float32)),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq))
                                  .astype(np.int32))}
    elif cfg.frontend == "vision":
        st = seq - cfg.n_patches
        out = {"tokens": jnp.asarray(toks[:, :st]),
               "patches": jnp.asarray(rng.normal(
                   size=(batch, cfg.n_patches, cfg.frontend_dim))
                   .astype(np.float32)),
               "labels": jnp.asarray(toks[:, :st])}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--theta-d-max", type=float, default=0.6)
    ap.add_argument("--theta-u", type=float, default=0.35)
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, local_iters=args.tau)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    dcfg = D.DistConfig(theta_d=0.0, theta_u=args.theta_u,
                        local_lr=args.lr,
                        use_error_feedback=args.error_feedback)

    rng = RNG.stream(args.seed, RNG.KIND_DATASET)
    with jax.set_mesh(mesh):
        params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
        state = D.init_state(params, dcfg, mesh)
        step_fn = jax.jit(D.make_train_step(cfg, dcfg, mesh))

        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if mgr:
            got = mgr.restore_latest(state)
            if got:
                state, start = got
                print(f"[train] resumed from checkpoint step {start}")

        # Caesar round plan: staleness of the cohort grows when it skips
        # rounds; here the single cohort participates every round ⇒ Eq.3
        # with δ=1 after warmup. Precomputed in one shot: the former
        # per-step float(download_ratio(...)) blocked the loop on a jitted
        # scalar every round (REP006).
        ts = jnp.maximum(jnp.arange(max(args.steps, 1), dtype=jnp.int32), 1)
        td_sched = np.asarray(jax.vmap(
            lambda tt: ST.download_ratio(jnp.int32(1), tt,
                                         args.theta_d_max))(ts))
        for t in range(start, args.steps):
            theta_d = float(td_sched[t]) if t > 0 else 0.0
            state = dataclasses.replace(state, theta_d=jnp.float32(theta_d))
            batch = make_batch(rng, cfg, args.batch, args.seq)
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            # per-step loss print is the point of this launcher; the sync
            # is the logging cadence, not an accident
            loss = float(metrics["loss"])  # repro: noqa=REP006
            print(f"[train] step {t:4d} loss={loss:.4f} θ_d={theta_d:.3f} "
                  f"θ_u={args.theta_u} ({time.time()-t0:.2f}s)", flush=True)
            if mgr and (t + 1) % args.ckpt_every == 0:
                mgr.save(state, t + 1)
                print(f"[train] checkpointed step {t+1}")
        if mgr:
            mgr.save(state, args.steps)
    print("[train] done")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
