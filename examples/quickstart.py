"""Quickstart: the paper's word-count example on both execution levels.

1. Cluster level — the full pub/sub protocol: hiring, encrypted code/data
   provisioning, mapper-side shuffle, EOS counting (paper Figs. 3-4), with
   the user logic shipped as a <30-LOC script (paper Listings 1-2).
2. Device level — the same job as one jitted shard_map pipeline with the
   shuffle payload ChaCha20-encrypted on the wire.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

import jax

from repro.compat import make_mesh
from repro.compile_cache import enable_compile_cache
from repro.core.shuffle import SecureShuffleConfig
from repro.core.wordcount import wordcount
from repro.crypto import chacha
from repro.runtime.jobs import WORDCOUNT_MAP, WORDCOUNT_REDUCE, make_cluster, run_wordcount

LINES = [
    "the quick brown fox jumps over the lazy dog",
    "mapreduce inside enclaves keeps the data private",
    "the router only ever sees ciphertext",
] * 5


def main():
    enable_compile_cache()
    print("=== cluster level (pub/sub protocol, simulated nodes) ===")
    print(f"user map script:\n{WORDCOUNT_MAP}")
    cluster, client, _ = make_cluster(8)
    counts, info = run_wordcount(cluster, client, LINES, n_mappers=5, n_reducers=3)
    top = sorted(counts.items(), key=lambda kv: -kv[1])[:5]
    print(f"job finished in {info['elapsed']*1e3:.2f} virtual ms; top words: {top}")
    st = cluster.router.stats
    print(f"router: {st.publications} publications, {st.deliveries} deliveries, "
          f"{st.wire_bytes} wire bytes (all payloads encrypted)")

    print("\n=== device level (shard_map engine, encrypted all_to_all) ===")
    vocab = 1000
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, 20000, dtype=np.int32)
    mesh = make_mesh((1,), ("data",))
    secure = SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x01" * 12),
    )
    hist, dropped = wordcount(tokens, vocab, mesh, secure=secure)
    assert int(dropped) == 0
    ref = np.bincount(tokens, minlength=vocab)
    np.testing.assert_array_equal(np.asarray(hist), ref)
    print(f"token histogram verified over {len(tokens)} tokens, 0 dropped pairs")


if __name__ == "__main__":
    main()
