"""End-to-end Track-B driver: cohort-mode Caesar training of a (reduced)
qwen1.5-4b for a few hundred steps with checkpoint/restart.

This is the 100M-class end-to-end training example (≈67M params at the
default overrides; push --steps a few hundred for a real run).

  PYTHONPATH=src python examples/train_lm_cohort.py [--steps 300]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.checkpoint.manager import CheckpointManager
from repro.core import rng as RNG
from repro.fl import distributed as D
from repro.launch.mesh import make_local_mesh
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/caesar_lm_ckpt")
    args = ap.parse_args()

    # ≈67M params: 8 layers, d=512, vocab 32768 (qwen family, shrunk)
    cfg = dataclasses.replace(
        configs.get("qwen1.5-4b"), n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=8, d_head=64, d_ff=2048, vocab=32768, dtype="float32",
        remat=False, local_iters=1, name="qwen-115m")
    n_params = sum(l.size for l in jax.tree.leaves(M.init_abstract(cfg)))
    print(f"model: {cfg.name}, {n_params/1e6:.1f}M params")

    mesh = make_local_mesh()
    dcfg = D.DistConfig(theta_d=0.3, theta_u=0.35, local_lr=3e-3,
                        use_error_feedback=True)
    rng = RNG.stream(0, RNG.KIND_DATASET)
    with jax.set_mesh(mesh):
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        state = D.init_state(params, dcfg, mesh)
        step_fn = jax.jit(D.make_train_step(cfg, dcfg, mesh))
        mgr = CheckpointManager(args.ckpt, keep=2)
        start = 0
        got = mgr.restore_latest(state)
        if got:
            state, start = got
            print(f"resumed at step {start}")
        # simple learnable stream: periodic token patterns + noise
        def batch_at(t):
            base = (np.arange(args.seq)[None] * (1 + t % 7)) % 1024
            toks = (base + rng.integers(0, 4, (args.batch, args.seq))) % cfg.vocab
            toks = toks.astype(np.int32)
            return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
        t0 = time.time()
        for t in range(start, args.steps):
            state, m = step_fn(state, batch_at(t))
            if t % 20 == 0 or t == args.steps - 1:
                # logging boundary, cadence-limited to every 20 steps
                print(f"step {t:4d} loss={float(m['loss']):.4f} "  # repro: noqa=REP006
                      f"({time.time()-t0:.0f}s)", flush=True)
            if (t + 1) % 100 == 0:
                mgr.save(state, t + 1)
        mgr.save(state, args.steps)
    print("done")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
