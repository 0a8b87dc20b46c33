"""End-to-end training driver: secure-ingest LM training with checkpoints.

Trains a reduced config of any assigned architecture on synthetic structured
tokens for a few hundred steps, with the paper's data path (batches encrypted
on the host, decrypted in-graph), MAC-verified checkpointing, and restart.

Run:  PYTHONPATH=src python examples/train_lm.py --arch rwkv6-1.6b --steps 200
      PYTHONPATH=src python examples/train_lm.py --arch granite-moe-3b-a800m
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.crypto.keys import make_session_keys
from repro.data.pipeline import SecureShardedSource
from repro.data.synthetic import synthetic_tokens
from repro.models.lm import init_params
from repro.optim.adamw import adamw_init
from repro.train.step import SecureIngest, make_train_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.family == "audio":
        raise SystemExit("audio arch: use serve_lm.py (training driver is LM-style)")
    mesh = make_mesh((1,), ("data",))

    session = make_session_keys(b"\x42" * 32)
    ingest = SecureIngest(key_words=session.words("data"),
                          nonce_words=session.nonce_words("data", 0))
    toks = synthetic_tokens(200_000, cfg.vocab_size, seed=0)
    src = SecureShardedSource(toks, batch=args.batch, seq=args.seq, session=session)

    step_fn, _, _ = make_train_step(
        cfg, mesh, secure_ingest=ingest, peak_lr=1e-3, warmup=20,
        total_steps=args.steps, donate=False,
    )
    params = init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    mgr = CheckpointManager(args.ckpt_dir)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} (reduced) params={n_params/1e6:.2f}M "
          f"secure_ingest=on vocab={cfg.vocab_size}")

    t0 = time.perf_counter()
    first_loss = None
    for i in range(args.steps):
        batch = src.next_batch()  # ciphertext + counter
        params, opt, metrics = step_fn(params, opt, batch, jnp.int32(i))
        if i == 0:
            first_loss = float(metrics["loss"])
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}")
        if (i + 1) % args.ckpt_every == 0:
            path = mgr.save(i + 1, (params, opt),
                            extra={"step": i + 1, "data_cursor": src.state})
            print(f"  checkpoint -> {path}")
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])
    print(f"\n{args.steps} steps in {dt:.1f}s ({dt/args.steps*1e3:.0f} ms/step); "
          f"loss {first_loss:.3f} -> {final_loss:.3f}")
    assert final_loss < first_loss, "training should reduce loss"


if __name__ == "__main__":
    main()
