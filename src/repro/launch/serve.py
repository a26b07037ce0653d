"""Serving launcher: batched prefill + decode loop at smoke scale.

``python -m repro.launch.serve --arch qwen2-7b --reduced --tokens 32``
loads a reduced model, prefills a batch of prompts and decodes N tokens,
reporting per-token latency. The production path is the same decode_step
the dry-run lowers at (16,16)/(2,16,16).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_cache, init_params
from repro.train import make_decode_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.key(args.seed))
    B = args.batch
    max_len = args.prompt_len + args.tokens
    cache = init_cache(cfg, B, max_len, dtype=jnp.float32)
    memory = (jnp.zeros((B, 8, cfg.d_model), jnp.float32)
              if cfg.family == "encdec" else None)

    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, args.prompt_len)),
                         jnp.int32)
    fn = jax.jit(make_decode_fn(cfg, dtype=jnp.float32),
                 static_argnames=())

    # prefill via repeated decode (exact; batched-prefill path is the
    # dry-run's prefill cell)
    tok = prompt[:, :1]
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, cache = fn(params, cache, prompt[:, t:t + 1],
                           jnp.int32(t), memory)
    out = []
    for t in range(args.tokens):
        nxt = jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None] \
            .astype(jnp.int32)
        out.append(np.asarray(nxt))
        logits, cache = fn(params, cache, nxt,
                           jnp.int32(args.prompt_len + t), memory)
    dt = time.time() - t0
    total = args.prompt_len + args.tokens
    print(f"arch={cfg.name} batch={B} {total} steps in {dt:.2f}s "
          f"({1000*dt/total:.1f} ms/token-step)")
    gen = np.concatenate(out, axis=1)
    print("sample:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
