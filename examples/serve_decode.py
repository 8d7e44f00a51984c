"""Serving example: batched autoregressive decoding with a KV cache
(GQA + MLA + SSM state caches all supported; Pallas flash-decode kernel is
exercised directly at the end).

  PYTHONPATH=src python examples/serve_decode.py --arch mamba2-780m
"""
import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = configs.get(args.arch).smoke()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = jax.random.PRNGKey(1)
    prompt = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab)
    cache = M.init_cache(cfg, args.batch,
                         args.prompt_len + args.new_tokens)
    step = jax.jit(lambda p, c, t, l: M.decode_step(p, c, {"tokens": t}, l,
                                                    cfg))
    length = jnp.zeros(args.batch, jnp.int32)
    # prefill token-by-token (simple), then sample greedily
    tok = prompt[:, :1]
    out = []
    t0 = time.time()
    for i in range(args.prompt_len + args.new_tokens - 1):
        logits, cache = step(params, cache, tok, length)
        length = length + 1
        if i + 1 < args.prompt_len:
            tok = prompt[:, i + 1:i + 2]
        else:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out.append(tok)
    toks_s = args.batch * len(out) / (time.time() - t0)
    print(f"[{cfg.name}] generated {len(out)} tokens/seq × {args.batch} seqs "
          f"({toks_s:.1f} tok/s on CPU)")
    print("sample:", jnp.concatenate(out, 1)[0][:16].tolist())

    # Pallas flash-decode kernel: compiled on a TPU, the Pallas interpreter
    # elsewhere
    from repro.kernels import ops
    interpret = jax.default_backend() != "tpu"
    B, H, Hkv, D, S = 2, 8, 4, 64, 2048
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    o = ops.decode_attention(q, k, v, jnp.array([S, S // 2]),
                             interpret=interpret)
    print("pallas decode_attention output:", o.shape, "finite:",
          bool(jnp.isfinite(o).all()))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
