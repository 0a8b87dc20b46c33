"""Batched serving driver: prefill + sampled decode on any assigned arch.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-1.2b --tokens 32
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models.lm import init_params
from repro.serve.engine import decode_step, init_cache, prefill


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-1.2b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = init_params(cfg, jax.random.key(0))
    b, tp = args.batch, args.prompt_len
    smax = tp + args.tokens + 1

    key = jax.random.key(1)
    prompts = jax.random.randint(key, (b, tp), 0, cfg.vocab_size, jnp.int32)
    frames = None
    if cfg.family == "audio":
        frames = jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model), jnp.float32)

    cache = init_cache(cfg, b, smax)
    dec = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))

    t0 = time.perf_counter()
    logits, cache = prefill(cfg, params, prompts, cache, frames=frames)
    t_prefill = time.perf_counter() - t0

    out = []
    cur = None
    t0 = time.perf_counter()
    for i in range(args.tokens):
        key, sub = jax.random.split(key)
        lg = logits if cur is None else lg_step
        nxt = jax.random.categorical(sub, lg / args.temperature, axis=-1).astype(jnp.int32)
        nxt = jnp.clip(nxt, 0, cfg.vocab_size - 1)
        out.append(np.asarray(nxt))
        lg_step, cache = dec(params, cache, nxt[:, None])
        cur = True
    jax.block_until_ready(lg_step)
    t_decode = time.perf_counter() - t0

    gen = np.stack(out, 1)
    print(f"arch={cfg.name} (reduced)  batch={b}  prompt={tp}  generated={args.tokens}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: {t_decode/args.tokens*1e3:.1f} ms/token "
          f"({b*args.tokens/t_decode:.1f} tok/s aggregate)")
    for row in gen[:2]:
        print("sample:", row[:16].tolist(), "...")


if __name__ == "__main__":
    main()
