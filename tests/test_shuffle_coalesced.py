"""Layout-equivalence tests: coalesced single-wire vs per-leaf secure shuffle.

The coalesced wire concatenates every leaf's word rows PACKED into ONE
(R, payload_words) buffer — zero pad bytes travel — encrypts it with one
keystream launch whose per-block counter bases reproduce the per-leaf
counter assignment (keystream is derived block-aligned and sliced to the
packed payload), and moves it with exactly one `lax.all_to_all` per round;
plaintext mode shares the same packed wire topology minus the crypt. These
tests prove the layouts are interchangeable at the BIT level — identical
ciphertext per leaf region, identical decrypted trees, identical
multi-round k-means — across leaf dtypes (u32/i32/f32/bf16), odd word
counts, round ids, and both keystream impls; and they prove the structural
claims (one collective per round, secure AND plaintext; two launches per
secure round) by jaxpr inspection, not accounting.

Property tests use hypothesis when installed and the seeded deterministic
fallback from tests/conftest.py otherwise (same pattern as
tests/test_shuffle_impls.py).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core import shuffle
from repro.core.shuffle import (
    COALESCE_ENV,
    SecureShuffleConfig,
    keyed_all_to_all,
    record_wire_bytes,
    resolve_coalesce,
)
from repro.crypto import chacha
from repro.tools.jaxprs import count_primitives

try:
    from repro.kernels.chacha20 import ops  # noqa: F401
except ImportError as e:  # e.g. no Pallas frontend for this platform
    pytest.skip(f"Pallas chacha20 kernel unavailable: {e}", allow_module_level=True)

KW = chacha.key_to_words(bytes(range(32)))
NW = chacha.nonce_to_words(b"\x07" * 12)


def _cfg(impl: str, coalesce="auto", counter0: int = 100) -> SecureShuffleConfig:
    return SecureShuffleConfig(key_words=KW, nonce_words=NW, counter0=counter0,
                               impl=impl, coalesce=coalesce)


def _random_tree(rng, r: int, c: int):
    """A 4-leaf tree covering u32/i32/f32/bf16 wire forms; odd `c` exercises
    odd word counts (bf16 packs to a half-word tail) and sub-block rows."""
    return {
        "f": jnp.asarray(rng.normal(size=(r, c, 3)).astype(np.float32)),
        "h": jnp.asarray(rng.normal(size=(r, c)).astype(np.float32)).astype(jnp.bfloat16),
        "k": jnp.asarray(rng.integers(-5, 100, (r, c)), jnp.int32),
        "u": jnp.asarray(rng.integers(0, 2**32, (r, c), dtype=np.uint32)),
    }


# --- ciphertext-level equivalence ---------------------------------------------


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 2**32 - 1))
def test_coalesced_ciphertext_matches_per_leaf_segments(seed, round_id):
    """Every leaf's region of the coalesced ciphertext is BIT-identical to
    that leaf's per-leaf-path ciphertext, under both impls, for arbitrary
    round ids — the counter-space contract holds across the re-layout."""
    rng = np.random.default_rng(seed)
    r, c = 3, 5
    tree = _random_tree(rng, r, c)
    nonce_ids = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
    ctr_rows = jnp.asarray(rng.integers(0, 2**16, (r,), dtype=np.uint32))
    rid = jnp.uint32(round_id)

    wires, meta, _ = shuffle._pack_wire(tree)
    wire, layout, _ = shuffle._pack_wire_coalesced(tree)
    out = {}
    for impl in ("pallas-interpret", "jnp"):
        enc_leaf = shuffle._crypt_wires(wires, meta, _cfg(impl), nonce_ids,
                                        ctr_rows, rid)
        enc_co = np.asarray(shuffle._crypt_wire_coalesced(
            wire, layout, _cfg(impl), nonce_ids, ctr_rows, rid))
        for leaf_ct, m in zip(enc_leaf, layout.leaves):
            _shape, _dtype, _pad, word_start, n_words, _blocks, _ks = m
            np.testing.assert_array_equal(
                np.asarray(leaf_ct), enc_co[:, word_start:word_start + n_words])
        out[impl] = enc_co
    np.testing.assert_array_equal(out["pallas-interpret"], out["jnp"])


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_coalesced_cross_impl_roundtrip(seed):
    """The jnp oracle decrypts what the Pallas lane kernel encrypted on the
    coalesced wire, back to the exact input bits (incl. bf16 NaN-safety:
    the wire is opaque u32 end to end)."""
    rng = np.random.default_rng(seed)
    r, c = 4, 7
    tree = _random_tree(rng, r, c)
    nonce_ids = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
    ctr_rows = jnp.asarray(rng.integers(0, 2**16, (r,), dtype=np.uint32))
    rid = jnp.uint32(rng.integers(0, 2**32))

    wire, layout, treedef = shuffle._pack_wire_coalesced(tree)
    enc = shuffle._crypt_wire_coalesced(wire, layout, _cfg("pallas-interpret"),
                                        nonce_ids, ctr_rows, rid)
    dec = shuffle._crypt_wire_coalesced(enc, layout, _cfg("jnp"),
                                        nonce_ids, ctr_rows, rid)
    back = shuffle._unpack_wire_coalesced(dec, layout, treedef)
    for leaf, orig in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(
            np.asarray(leaf).view(np.uint8), np.asarray(orig).view(np.uint8))


def test_coalesced_layout_block_alignment():
    """Static layout facts: wire segments are PACKED (zero alignment pad on
    the wire), keystream segments start at block boundaries, counter bases
    reproduce the per-leaf offsets (Σ preceding blocks·R), rowmuls carry
    each leaf's blocks-per-row, zero-size leaves contribute nothing."""
    r, c = 3, 5
    tree = {
        "a": jnp.zeros((r, c), jnp.int32),        # 5 words  -> 1 block
        "b": jnp.zeros((r, c, 7), jnp.float32),   # 35 words -> 3 blocks
        "e": jnp.zeros((r, c, 0), jnp.float32),   # 0 words  -> 0 blocks
    }
    wire, layout, _ = shuffle._pack_wire_coalesced(tree)
    # the wire carries exactly the payload words, back-to-back
    assert wire.shape == (r, layout.payload_words)
    assert layout.payload_words == 5 + 35 + 0
    # the keystream layout stays block-aligned: 4 blocks = 64 words
    assert layout.total_blocks == 4 and layout.total_words == 64
    by_start = sorted(layout.leaves, key=lambda m: m[3])
    assert [m[3] for m in by_start] == [0, 5, 40]   # packed wire offsets
    assert [m[6] for m in by_start] == [0, 16, 64]  # aligned keystream offsets
    assert all(m[6] % 16 == 0 for m in layout.leaves)
    np.testing.assert_array_equal(
        layout.ctr_base, np.array([0, 1 * r + 0, 1 * r + 1, 1 * r + 2], np.uint32))
    np.testing.assert_array_equal(
        layout.ctr_rowmul, np.array([1, 3, 3, 3], np.uint32))


# --- end-to-end through the mesh ----------------------------------------------


def test_keyed_all_to_all_layouts_agree_end_to_end():
    """Plain (coalesced default AND per-leaf), coalesced-secure, and
    per-leaf-secure exchanges return the same bits, and the wire records
    carry the structural counts (1 vs n_leaves collectives, 2 vs 2·n_leaves
    launches, zero pad bytes on the packed wire) plus the per-leaf payload
    breakdown."""
    mesh = make_mesh((1,), ("data",))
    rng = np.random.default_rng(11)
    tree = _random_tree(rng, 1, 5)
    specs = jax.tree.map(lambda _: P("data"), tree)

    def run(sec, coalesce=None):
        body = lambda t: keyed_all_to_all(t, "data", sec,
                                          round_index=jnp.uint32(7),
                                          coalesce=coalesce)
        fn = jax.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                           check_vma=False)
        return jax.jit(fn)(tree)

    with record_wire_bytes() as recs:
        out_plain = run(None)                 # plaintext, coalesced default
        out_plain_pl = run(None, coalesce=False)
        out_co = run(_cfg("pallas-interpret", True))
        out_pl = run(_cfg("pallas-interpret", False))
    ref = [np.asarray(l).view(np.uint8) for l in jax.tree.leaves(out_plain)]
    for other in (out_plain_pl, out_co, out_pl):
        for a, b in zip(ref, jax.tree.leaves(other)):
            np.testing.assert_array_equal(a, np.asarray(b).view(np.uint8))

    plain_co, plain_pl, co, pl = recs
    n_leaves = len(jax.tree.leaves(tree))
    assert plain_co["coalesced"] and co["coalesced"]
    assert not plain_pl["coalesced"] and not pl["coalesced"]
    # plaintext coalesced: same single-wire topology, no keystream
    assert plain_co["collectives"] == 1 and plain_co["keystream_launches"] == 0
    assert plain_pl["collectives"] == n_leaves
    assert plain_pl["keystream_launches"] == 0
    assert co["collectives"] == 1 and co["keystream_launches"] == 2
    assert pl["collectives"] == n_leaves
    assert pl["keystream_launches"] == 2 * n_leaves
    # zero CTR expansion, leaf by leaf, on both secure layouts
    assert co["per_leaf"] == pl["per_leaf"]
    assert co["bytes"] == pl["bytes"] == sum(co["per_leaf"])
    # the packed wire carries ZERO pad bytes — secure and plaintext alike
    for rec in (plain_co, plain_pl, co, pl):
        assert rec["pad_bytes"] == 0 and rec["wire_bytes"] == rec["bytes"]


# --- structural proof: one all_to_all per secure round ------------------------


@pytest.mark.parametrize("coalesce,want_a2a,want_launches",
                         [(True, 1, 2), (False, 3, 6)])
def test_jaxpr_collectives_per_secure_round(coalesce, want_a2a, want_launches):
    """Jaxpr inspection of the fused driver round: the ≥3-leaf k-means tree
    ({k} + {s, c}) traces exactly ONE all_to_all and TWO pallas_call
    keystream launches per secure round when coalesce=True — and the
    per-leaf oracle traces one collective and two launches PER LEAF."""
    from repro.core.driver import make_iterative_runner
    from repro.core.kmeans import generate_points, make_kmeans_iterative_spec

    mesh = make_mesh((1,), ("data",))
    pts, _ = generate_points(64, 4, seed=5)
    inputs = {"p": jnp.asarray(pts), "w": jnp.ones((64,), jnp.float32)}
    spec = make_kmeans_iterative_spec(4, 1, n_rounds=2)
    c0 = jnp.asarray(pts[:4])
    runner = make_iterative_runner(
        spec, mesh, secure=_cfg("pallas-interpret", coalesce))
    jaxpr = jax.make_jaxpr(runner.abstract_fn)(inputs, c0, jnp.uint32(0))
    # the scan body traces once, so whole-program counts ARE per-round counts
    assert count_primitives(jaxpr, "all_to_all") == want_a2a
    assert count_primitives(jaxpr, "pallas_call") == want_launches


@pytest.mark.parametrize("coalesce,want_a2a", [(True, 1), (False, 3)])
def test_jaxpr_collectives_per_plaintext_round(coalesce, want_a2a):
    """Plaintext (`secure=None`) rounds ride the same packed single-wire
    topology: ONE all_to_all per round by default (per-leaf with
    coalesce=False), and ZERO keystream launches either way — so a
    secure-vs-plain jaxpr diff isolates the crypt, not the wire shape."""
    from repro.core.driver import make_iterative_runner
    from repro.core.kmeans import generate_points, make_kmeans_iterative_spec

    mesh = make_mesh((1,), ("data",))
    pts, _ = generate_points(64, 4, seed=5)
    inputs = {"p": jnp.asarray(pts), "w": jnp.ones((64,), jnp.float32)}
    spec = make_kmeans_iterative_spec(4, 1, n_rounds=2)
    c0 = jnp.asarray(pts[:4])
    runner = make_iterative_runner(spec, mesh, secure=None, coalesce=coalesce)
    jaxpr = jax.make_jaxpr(runner.abstract_fn)(inputs, c0, jnp.uint32(0))
    assert count_primitives(jaxpr, "all_to_all") == want_a2a
    assert count_primitives(jaxpr, "pallas_call") == 0


# --- selector resolution ------------------------------------------------------


def test_resolve_coalesce_env_and_explicit(monkeypatch, no_calibration):
    monkeypatch.delenv(COALESCE_ENV, raising=False)
    assert resolve_coalesce("auto") is True
    assert resolve_coalesce(None) is True
    assert resolve_coalesce(True) is True
    assert resolve_coalesce(False) is False

    monkeypatch.setenv(COALESCE_ENV, "0")
    assert resolve_coalesce("auto") is False
    # an explicit bool always wins over the environment
    assert resolve_coalesce(True) is True
    monkeypatch.setenv(COALESCE_ENV, "true")
    assert resolve_coalesce("auto") is True

    monkeypatch.setenv(COALESCE_ENV, "sideways")
    with pytest.raises(ValueError, match=rf"\${COALESCE_ENV}='sideways'"):
        resolve_coalesce("auto")
    monkeypatch.delenv(COALESCE_ENV, raising=False)
    with pytest.raises(ValueError) as ei:
        resolve_coalesce("sideways")
    assert COALESCE_ENV not in str(ei.value)


def test_with_coalesce_override():
    cfg = _cfg("auto")
    assert cfg.with_coalesce(None) is cfg
    assert cfg.with_coalesce("auto") is cfg
    over = cfg.with_coalesce(False)
    assert over.coalesce is False and over.impl == cfg.impl
    assert cfg.coalesce == "auto"  # frozen: original untouched


# --- multi-round driver: fused secure k-means identical across layouts --------


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["pallas-interpret", "jnp"])
def test_secure_kmeans_multiround_bitexact_across_layouts(impl):
    """Acceptance anchor: a fused multi-round secure k-means run produces
    bit-identical centers/shifts whether the wire is coalesced or per-leaf
    (exercises the `coalesce` plumbing through driver entry points), under
    both keystream impls."""
    from repro.core.driver import run_iterative_mapreduce
    from repro.core.kmeans import generate_points, make_kmeans_iterative_spec

    mesh = make_mesh((1,), ("data",))
    pts, _ = generate_points(256, 4, seed=5)
    inputs = {"p": jnp.asarray(pts), "w": jnp.ones((256,), jnp.float32)}
    spec = make_kmeans_iterative_spec(4, 1, n_rounds=2)
    c0 = jnp.asarray(pts[:4])
    out = {}
    for coalesce in (True, False):
        final, aux, dropped = run_iterative_mapreduce(
            spec, inputs, c0, mesh, secure=_cfg(impl), coalesce=coalesce)
        assert int(np.asarray(dropped).sum()) == 0
        out[coalesce] = (np.asarray(final), np.asarray(aux["shift"]),
                         np.asarray(aux["centers"]))
    for a, b in zip(out[True], out[False]):
        np.testing.assert_array_equal(a, b)
