"""Coalesced single-wire vs per-leaf secure shuffle: the boundary-crossing tax.

The paper's security argument lives at the mapper→reducer boundary; this
benchmark measures what one secure round PAYS to cross it under the two wire
layouts (`core/shuffle.py`):

  * structural counts — all_to_all collectives and keystream launches per
    secure round for the ≥3-leaf k-means tree through the fused driver,
    proven two independent ways: jaxpr inspection (`repro.tools.jaxprs`)
    and the shuffle's trace-time wire accounting. Coalesced must trace
    exactly 1 collective + 2 launches per round vs n_leaves and 2·n_leaves
    on the per-leaf path (asserted);
  * bytes per round — payload vs on-the-wire bytes (the coalesced layout's
    only overhead is the ≤15-word/leaf block-alignment pad), per-leaf
    breakdown included so zero CTR expansion stays auditable leaf by leaf;
  * steady-state per-round time — an isolated secure shuffle (encrypt →
    all_to_all → decrypt under shard_map) timed for coalesced vs per-leaf
    × keystream impls (pallas — compiled on the TPU, interpreted on the
    CPU — / jnp) on a multi-device mesh (`benchmarks/multidevice.py`: every
    attached chip in this process, or 8 forced host devices in a CPU
    child). A multi-device mesh is the honest harness: the shuffle is a COLLECTIVE path, and on a 1-device
    in-process mesh the timing measures XLA's thread-pool parallelism
    across per-leaf fusions instead of the wire (the per-leaf path's 3
    independent keystreams fan out over idle cores there, an artifact no
    real mesh reproduces — every device is busy with its own shard).
    Coalesced must not be slower than per-leaf (asserted, min-of-reps;
    measured on the CPU ~1.7x faster on the interpreted kernel and ~4x on
    jnp, with ~3x faster secure compiles).

Machine-readable output: `run()` fills the module-level `LAST_METRICS`
dict, which `benchmarks/run.py` serializes to BENCH_shuffle.json (uploaded
by the CI bench-smoke lane alongside BENCH_driver.json).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.multidevice import run_section
from repro.compat import make_mesh
from repro.core.driver import make_iterative_runner
from repro.core.kmeans import generate_points, make_kmeans_iterative_spec
from repro.core.shuffle import SecureShuffleConfig, record_wire_bytes
from repro.crypto import chacha
from repro.tools.jaxprs import count_primitives

# Filled by run(); serialized by benchmarks/run.py into BENCH_shuffle.json.
LAST_METRICS: dict = {}

IMPLS = ("pallas", "jnp")

_TIMING_SECTION = """
import time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh
from repro.core.shuffle import SecureShuffleConfig, keyed_all_to_all
from repro.crypto import chacha

mesh = make_mesh((n_dev,), ("data",))
rng = np.random.default_rng(0)
tree = {"k": jnp.asarray(rng.integers(0, 100, (n_dev * n_dev, c)), jnp.int32),
        "v": {"s": jnp.asarray(rng.normal(size=(n_dev * n_dev, c, d)).astype(np.float32)),
              "c": jnp.asarray(rng.normal(size=(n_dev * n_dev, c)).astype(np.float32))}}
specs = jax.tree.map(lambda _: P("data"), tree)
out = {}
for impl in impls:
    out[impl] = {}
    fns = {}
    for coalesce, label in ((True, "coalesced"), (False, "per_leaf")):
        sec = SecureShuffleConfig(key_words=chacha.key_to_words(bytes(range(32))),
                                  nonce_words=chacha.nonce_to_words(b"\\x06" * 12),
                                  impl=impl, coalesce=coalesce)
        body = lambda t, sec=sec: keyed_all_to_all(t, "data", sec,
                                                   round_index=jnp.uint32(3))
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                   out_specs=specs, check_vma=False))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(tree))
        out[impl][label] = {"compile_s": time.perf_counter() - t0}
        fns[label] = fn
    # INTERLEAVED trials: time both layouts back-to-back under the same
    # machine conditions (sequential phases drift by +-60% on shared CI
    # boxes and would swamp the ~1.3x layout difference), min over all
    best = {label: float("inf") for label in fns}
    for _ in range(3):
        for label, fn in fns.items():
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(tree))
                best[label] = min(best[label], time.perf_counter() - t0)
    for label in fns:
        out[impl][label]["us_per_round"] = best[label] * 1e6
"""


def _cfg(impl: str, coalesce) -> SecureShuffleConfig:
    return SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x06" * 12),
        impl=impl, coalesce=coalesce,
    )


def run(smoke: bool = False):
    global LAST_METRICS
    rows = []
    metrics: dict = {"smoke": smoke, "kmeans_tree": {}, "micro_shuffle": {}}
    mesh = make_mesh((1,), ("data",))

    # --- structural counts: the 3-leaf k-means tree through the driver -------
    # One fused secure round of the paper's workload shuffles the tree
    # {k: (R,C) i32, v: {s: (R,C,d) f32, c: (R,C) f32}} — 3 leaves. The scan
    # body traces once, so whole-program jaxpr counts ARE per-round counts.
    n, k = (512 if smoke else 2048), 8
    pts, _ = generate_points(n, k, seed=6)
    inputs = {"p": jnp.asarray(pts), "w": jnp.ones((n,), jnp.float32)}
    spec = make_kmeans_iterative_spec(k, 1, n_rounds=2)
    c0 = jnp.asarray(pts[:k])
    for coalesce, label in ((True, "coalesced"), (False, "per_leaf")):
        runner = make_iterative_runner(spec, mesh,
                                       secure=_cfg("pallas", coalesce))
        with record_wire_bytes() as recs:
            jaxpr = jax.make_jaxpr(runner.abstract_fn)(inputs, c0, jnp.uint32(0))
        a2a = count_primitives(jaxpr, "all_to_all")
        launches = count_primitives(jaxpr, "pallas_call")
        (rec,) = [r for r in recs if r["secure"]]
        assert a2a == rec["collectives"] and launches == rec["keystream_launches"], (
            "jaxpr and wire accounting disagree", a2a, launches, rec)
        metrics["kmeans_tree"][label] = {
            "n_leaves": rec["leaves"],
            "all_to_all_per_round": a2a,
            "keystream_launches_per_round": launches,
            "bytes_per_round": rec["bytes"],
            "wire_bytes_per_round": rec["wire_bytes"],
            "pad_bytes_per_round": rec["pad_bytes"],
            "per_leaf_bytes": rec["per_leaf"],
        }
        rows.append((f"shuffle_round_{label}", 0.0,
                     f"all_to_all={a2a};keystream_launches={launches};"
                     f"wire_bytes={rec['wire_bytes']}"))
    co, pl = metrics["kmeans_tree"]["coalesced"], metrics["kmeans_tree"]["per_leaf"]
    assert co["n_leaves"] >= 3
    assert co["all_to_all_per_round"] == 1 and co["keystream_launches_per_round"] == 2, co
    assert pl["all_to_all_per_round"] == pl["n_leaves"], pl
    assert pl["keystream_launches_per_round"] == 2 * pl["n_leaves"], pl
    # zero CTR ciphertext expansion, leaf by leaf, on both layouts
    assert co["per_leaf_bytes"] == pl["per_leaf_bytes"]
    assert co["bytes_per_round"] == pl["bytes_per_round"]

    # --- steady-state per-round time: isolated secure shuffle, multi-device --
    # The same 3-leaf tree shape over several devices (module docstring: why
    # 1-device in-process timing on the CPU would be a lie).
    n_dev = 8
    c, d = (64, 4) if smoke else (128, 8)
    reps = 5 if smoke else 10
    timing = run_section(_TIMING_SECTION,
                         dict(c=c, d=d, reps=reps, impls=IMPLS),
                         n_dev=n_dev, timeout=1800)
    n_dev = timing["devices"]["count"]
    metrics["micro_shuffle"] = {"n_dev": n_dev, "c": c, "d": d, "reps": reps,
                                **timing}
    for impl in IMPLS:
        per = timing[impl]
        speedup = per["per_leaf"]["us_per_round"] / max(
            per["coalesced"]["us_per_round"], 1e-9)
        per["speedup"] = speedup
        rows.append((f"shuffle_secure_round_{impl}_coalesced",
                     per["coalesced"]["us_per_round"],
                     f"speedup={speedup:.2f}x;"
                     f"compile={per['coalesced']['compile_s']:.1f}s"))
        rows.append((f"shuffle_secure_round_{impl}_per_leaf",
                     per["per_leaf"]["us_per_round"],
                     f"oracle;compile={per['per_leaf']['compile_s']:.1f}s"))
        assert per["coalesced"]["us_per_round"] <= per["per_leaf"]["us_per_round"], (
            f"coalesced secure round must not be slower than per-leaf on "
            f"{impl}: {per['coalesced']['us_per_round']:.1f}us vs "
            f"{per['per_leaf']['us_per_round']:.1f}us")

    LAST_METRICS = metrics
    return rows
