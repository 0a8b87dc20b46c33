"""Differential tests: 'pallas' vs 'jnp' secure-shuffle keystream backends.

The secure shuffle's counter-space layout (nonce word 0 ^= source id, word 1
^= round id, absolute per-row counter starts) is computed identically by the
Pallas rows kernel and the vmapped pure-jnp oracle, so the two backends must
be BIT-exact — across nonce ids, counter rows, round ids, leaf wire dtypes
(u32/i32/f32/bf16), and odd word counts. These tests are what make swapping
crypto backends safe: any divergence is a key/nonce/counter layout bug, not
a tolerance issue, hence `assert_array_equal` throughout.

Property tests use hypothesis when installed and the seeded deterministic
fallback from tests/conftest.py otherwise. RFC 8439 vectors anchor the new
`chacha20_xor_rows` entry point to the spec, not just to our own oracle.
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
    CHACHA_IMPL_ENV,
    SecureShuffleConfig,
    keyed_all_to_all,
    record_wire_bytes,
    resolve_chacha_impl,
)
from repro.crypto import chacha
from rfc_vectors import RFC_BLOCK_232, RFC_CIPHERTEXT, RFC_KEY, RFC_NONCE_232, RFC_NONCE_242, RFC_PLAINTEXT

try:
    from repro.kernels.chacha20 import ops
except ImportError as e:  # e.g. no Pallas frontend for this platform
    pytest.skip(f"Pallas chacha20 kernel unavailable: {e}", allow_module_level=True)

KW = chacha.key_to_words(bytes(range(32)))
NW = chacha.nonce_to_words(b"\x07" * 12)


def _cfg(impl: str, counter0: int = 100) -> SecureShuffleConfig:
    return SecureShuffleConfig(key_words=KW, nonce_words=NW, counter0=counter0,
                               impl=impl)


# --- chacha20_xor_rows: pallas vs jnp, property-driven ------------------------


# Fixed shape set (jit caches per shape; examples then only vary data):
# single word, one exact block, odd tail mid-block, multi-block odd tail.
_ROW_SHAPES = [(1, 1), (3, 16), (4, 49), (7, 100)]


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_xor_rows_bitexact_across_impls(seed):
    """Random rows/ids/counters (incl. odd n_words): identical ciphertext."""
    rng = np.random.default_rng(seed)
    state0 = ops.make_state0(KW, NW, 0)
    for r, n_words in _ROW_SHAPES:
        words = jnp.asarray(rng.integers(0, 2**32, (r, n_words), dtype=np.uint32))
        nonce_ids = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
        ctr_starts = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
        got = ops.chacha20_xor_rows(words, state0, nonce_ids, ctr_starts,
                                    impl="pallas", interpret=True)
        want = ops.chacha20_xor_rows(words, state0, nonce_ids, ctr_starts, impl="jnp")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 2**32 - 1))
def test_keystream_rows_bitexact_across_impls_and_rounds(seed, round_id):
    """`shuffle._keystream_rows` draws the same bits under every impl, for
    arbitrary round ids — and round None is round 0."""
    rng = np.random.default_rng(seed)
    r, blocks = 4, 3
    n_words = blocks * 16 - 7  # odd tail: keystream truncation must agree
    nonce_ids = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
    ctr_rows = jnp.asarray(rng.integers(0, 2**16, (r,), dtype=np.uint32))
    out = {}
    for impl in ("pallas-interpret", "jnp"):
        cfg = _cfg(impl)
        out[impl] = np.asarray(shuffle._keystream_rows(
            cfg, nonce_ids, ctr_rows, jnp.uint32(cfg.counter0), blocks, n_words,
            jnp.uint32(round_id)))
    np.testing.assert_array_equal(out["pallas-interpret"], out["jnp"])

    a = shuffle._keystream_rows(_cfg("pallas-interpret"), nonce_ids, ctr_rows,
                                jnp.uint32(100), blocks, n_words, None)
    b = shuffle._keystream_rows(_cfg("jnp"), nonce_ids, ctr_rows,
                                jnp.uint32(100), blocks, n_words, jnp.uint32(0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_crypt_wires_bitexact_across_impls_all_dtypes(seed):
    """Full wire path (pack -> encrypt) over u32/i32/f32/bf16 leaves, odd
    row word counts included: identical ciphertext, and the jnp oracle
    decrypts what the pallas path encrypted."""
    rng = np.random.default_rng(seed)
    r, c = 3, 5  # odd c: bf16 rows pack to a half-word tail
    tree = {
        "k": jnp.asarray(rng.integers(-5, 100, (r, c)), jnp.int32),
        "f": jnp.asarray(rng.normal(size=(r, c, 3)).astype(np.float32)),
        "h": jnp.asarray(rng.normal(size=(r, c)).astype(np.float32)).astype(jnp.bfloat16),
        "u": jnp.asarray(rng.integers(0, 2**32, (r, c), dtype=np.uint32)),
    }
    wires, meta, treedef = shuffle._pack_wire(tree)
    nonce_ids = jnp.asarray(rng.integers(0, 2**32, (r,), dtype=np.uint32))
    ctr_rows = jnp.asarray(rng.integers(0, 2**16, (r,), dtype=np.uint32))
    round_id = jnp.uint32(rng.integers(0, 2**32))

    enc_p = shuffle._crypt_wires(wires, meta, _cfg("pallas-interpret"),
                                 nonce_ids, ctr_rows, round_id)
    enc_j = shuffle._crypt_wires(wires, meta, _cfg("jnp"),
                                 nonce_ids, ctr_rows, round_id)
    for a, b in zip(enc_p, enc_j):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # cross-impl roundtrip: jnp decrypts pallas ciphertext to the exact bits
    dec = shuffle._crypt_wires(enc_p, meta, _cfg("jnp"), nonce_ids, ctr_rows, round_id)
    back = shuffle._unpack_wire(dec, meta, treedef)
    for leaf, orig in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(
            np.asarray(leaf).view(np.uint8), np.asarray(orig).view(np.uint8))


# --- RFC 8439 anchors ---------------------------------------------------------


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_rfc_block_through_rows_entry_point(impl):
    """§2.3.2 keystream block via chacha20_xor_rows (XOR with zeros)."""
    state0 = ops.make_state0(chacha.key_to_words(RFC_KEY),
                             chacha.nonce_to_words(RFC_NONCE_232), 0)
    zeros = jnp.zeros((1, 16), jnp.uint32)
    ks = ops.chacha20_xor_rows(zeros, state0, jnp.zeros((1,), jnp.uint32),
                               jnp.ones((1,), jnp.uint32), impl=impl, interpret=True)
    np.testing.assert_array_equal(np.asarray(ks)[0], RFC_BLOCK_232)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_rfc_encrypt_through_rows_entry_point(impl):
    """§2.4.2 sunscreen vector, plus the per-row nonce-XOR id contract:
    XORing id x into nonce word 0 == pre-XORing x into the base nonce."""
    n = len(RFC_PLAINTEXT)
    pt = np.frombuffer(RFC_PLAINTEXT + b"\x00" * ((-n) % 4), dtype="<u4")
    nw = chacha.nonce_to_words(RFC_NONCE_242)
    x = jnp.asarray(np.stack([pt, pt]))
    nid = np.uint32(0xDEADBEEF)
    state0 = ops.make_state0(chacha.key_to_words(RFC_KEY), nw, 0)
    state0_pre = ops.make_state0(chacha.key_to_words(RFC_KEY),
                                 nw ^ np.array([nid, 0, 0], np.uint32), 0)
    ct = ops.chacha20_xor_rows(x, state0, jnp.asarray([0, nid], jnp.uint32),
                               jnp.asarray([1, 1], jnp.uint32), impl=impl,
                               interpret=True)
    assert np.asarray(ct)[0].tobytes()[:n] == RFC_CIPHERTEXT
    ct_pre = ops.chacha20_xor_rows(x[1:], state0_pre, jnp.zeros((1,), jnp.uint32),
                                   jnp.ones((1,), jnp.uint32), impl=impl,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(ct)[1], np.asarray(ct_pre)[0])


# --- impl selection -----------------------------------------------------------


def test_impl_resolution_env_and_explicit(monkeypatch, no_calibration):
    monkeypatch.delenv(CHACHA_IMPL_ENV, raising=False)
    assert resolve_chacha_impl("auto")[0] == "pallas"
    assert resolve_chacha_impl("jnp") == ("jnp", True)
    assert resolve_chacha_impl("pallas-interpret") == ("pallas", True)

    monkeypatch.setenv(CHACHA_IMPL_ENV, "jnp")
    assert resolve_chacha_impl("auto") == ("jnp", True)
    # an explicit impl always wins over the environment
    assert resolve_chacha_impl("pallas-interpret") == ("pallas", True)

    monkeypatch.setenv(CHACHA_IMPL_ENV, "pallas-interpret")
    assert resolve_chacha_impl("auto") == ("pallas", True)

    with pytest.raises(ValueError):
        resolve_chacha_impl("vulkan")


def test_invalid_env_impl_error_names_the_env_var(monkeypatch):
    """A bad $REPRO_CHACHA_IMPL must be called out as coming from the
    environment (a generic message sends users hunting through code for a
    value they never passed)."""
    monkeypatch.setenv(CHACHA_IMPL_ENV, "vulkan")
    with pytest.raises(ValueError, match=rf"\${CHACHA_IMPL_ENV}='vulkan'"):
        resolve_chacha_impl("auto")
    # env value 'auto' is also invalid (it cannot self-resolve) and env-blamed
    monkeypatch.setenv(CHACHA_IMPL_ENV, "auto")
    with pytest.raises(ValueError, match=rf"\${CHACHA_IMPL_ENV}"):
        resolve_chacha_impl("auto")
    # an explicit bad impl is NOT blamed on the environment
    monkeypatch.delenv(CHACHA_IMPL_ENV, raising=False)
    with pytest.raises(ValueError) as ei:
        resolve_chacha_impl("vulkan")
    assert CHACHA_IMPL_ENV not in str(ei.value)


def test_with_impl_override():
    cfg = _cfg("auto")
    assert cfg.with_impl(None) is cfg
    assert cfg.with_impl("auto") is cfg
    over = cfg.with_impl("jnp")
    assert over.impl == "jnp" and over.counter0 == cfg.counter0
    assert cfg.impl == "auto"  # frozen: original untouched


# --- wire accounting: CTR ciphertext expansion is zero ------------------------


def test_wire_bytes_secure_equals_plain():
    """The secure wire form (packed u32 words) carries exactly the plaintext
    byte count for 4-byte leaf dtypes — CTR adds no ciphertext expansion."""
    mesh = make_mesh((1,), ("data",))
    tree = {
        "k": jnp.arange(8, dtype=jnp.int32).reshape(1, 8),
        "v": jnp.ones((1, 8, 2), jnp.float32),
    }
    specs = jax.tree.map(lambda _: P("data"), tree)

    def run(secure):
        body = lambda t: keyed_all_to_all(t, "data", secure)
        fn = jax.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                           check_vma=False)
        return jax.jit(fn)(tree)

    with record_wire_bytes() as recs:
        out_plain = run(None)
        out_sec = run(_cfg("pallas"))
    assert len(recs) == 2
    plain, sec = recs
    assert plain["secure"] is False and sec["secure"] is True
    assert plain["bytes"] == sec["bytes"] == 8 * 4 + 8 * 2 * 4
    # and the encrypted exchange is transparent end to end
    for a, b in zip(jax.tree.leaves(out_sec), jax.tree.leaves(out_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- multi-round driver: fused secure k-means identical under both impls ------


@pytest.mark.slow
def test_secure_kmeans_multiround_bitexact_across_impls():
    """Acceptance anchor: a fused multi-round secure k-means run produces
    bit-identical centers/shifts whether the shuffle keystream comes from the
    Pallas rows kernel or the jnp oracle (exercises the `chacha_impl`
    plumbing through driver entry points)."""
    from repro.core.driver import run_iterative_mapreduce
    from repro.core.kmeans import generate_points, make_kmeans_iterative_spec

    mesh = make_mesh((1,), ("data",))
    pts, _ = generate_points(256, 4, seed=5)
    inputs = {"p": jnp.asarray(pts), "w": jnp.ones((256,), jnp.float32)}
    spec = make_kmeans_iterative_spec(4, 1, n_rounds=2)
    c0 = jnp.asarray(pts[:4])
    out = {}
    for impl in ("pallas", "jnp"):
        final, aux, dropped = run_iterative_mapreduce(
            spec, inputs, c0, mesh, secure=_cfg("auto"), chacha_impl=impl)
        assert int(np.asarray(dropped).sum()) == 0
        out[impl] = (np.asarray(final), np.asarray(aux["shift"]),
                     np.asarray(aux["centers"]))
    for a, b in zip(out["pallas"], out["jnp"]):
        np.testing.assert_array_equal(a, b)
