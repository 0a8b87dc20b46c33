"""Secure MapReduce engine: bucketing invariants, wordcount, k-means."""

import re

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.compat import make_mesh
from repro.core.engine import MapReduceSpec, default_hash, identity_hash, run_mapreduce
from repro.core.kmeans import generate_points, kmeans_fit, kmeans_step_ref, make_kmeans_step
from repro.core.shuffle import SecureShuffleConfig, bucket_pack
from repro.core.wordcount import wordcount
from repro.crypto import chacha


def _mesh1():
    return make_mesh((1,), ("data",))


def _secure_cfg():
    return SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x07" * 12),
        counter0=100,
    )


# --- bucket_pack properties ---------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.integers(0, 31), min_size=1, max_size=64),
    st.integers(2, 8),
)
def test_bucket_pack_preserves_multiset(keys, r):
    keys = np.array(keys, np.int32)
    n = len(keys)
    vals = np.arange(n, dtype=np.float32)
    cap = n  # ample capacity
    bk, bv, dropped = bucket_pack(
        jnp.asarray(keys), jnp.asarray(keys) % r, jnp.asarray(vals), r, cap
    )
    assert int(dropped) == 0
    got_k = np.asarray(bk).reshape(-1)
    got_v = np.asarray(bv).reshape(-1)
    mask = got_k >= 0
    # multiset of (key, value) pairs preserved
    got = sorted(zip(got_k[mask].tolist(), got_v[mask].tolist()))
    want = sorted(zip(keys.tolist(), vals.tolist()))
    assert got == want
    # routing correct: row r contains only keys with bucket r
    for row in range(r):
        rk = np.asarray(bk)[row]
        assert np.all((rk < 0) | (rk % r == row))


def test_bucket_pack_overflow_counted():
    keys = jnp.zeros((10,), jnp.int32)  # all to bucket 0
    bk, _, dropped = bucket_pack(keys, keys, jnp.ones((10,)), 2, 4)
    assert int(dropped) == 6
    assert int((np.asarray(bk)[0] >= 0).sum()) == 4


def test_bucket_pack_all_invalid():
    """Every key negative (padding): empty buffer, nothing dropped, and all
    positions map to the R*C drop sentinel."""
    keys = jnp.full((6,), -1, jnp.int32)
    bk, bv, dropped, pos = bucket_pack(
        keys, jnp.zeros((6,), jnp.int32), jnp.arange(6, dtype=jnp.float32), 3, 2,
        return_positions=True,
    )
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(bk), np.full((3, 2), -1, np.int32))
    np.testing.assert_array_equal(np.asarray(bv), np.zeros((3, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(pos), np.full((6,), 3 * 2, np.int32))


def test_bucket_pack_exact_capacity_fill():
    """Each bucket receives exactly `capacity` items: every slot filled,
    zero drops — the boundary between lossless and overflow."""
    r, cap = 3, 4
    keys = jnp.arange(r * cap, dtype=jnp.int32)
    bucket = keys % r
    bk, bv, dropped = bucket_pack(keys, bucket, keys.astype(jnp.float32), r, cap)
    assert int(dropped) == 0
    bk = np.asarray(bk)
    assert (bk >= 0).all()  # no empty slot anywhere
    for row in range(r):
        np.testing.assert_array_equal(np.sort(bk[row]) % r, np.full(cap, row))


def test_bucket_pack_return_positions_under_overflow():
    """positions is the exact inverse map for surviving items; dropped and
    invalid items both map to the R*C sentinel."""
    r, cap = 2, 3
    #            kept x3 (bucket 0)   dropped   invalid   kept (bucket 1)
    keys = jnp.asarray([10, 11, 12, 13, 14, -1, 20], jnp.int32)
    bucket = jnp.asarray([0, 0, 0, 0, 0, 0, 1], jnp.int32)
    vals = jnp.arange(7, dtype=jnp.float32)
    bk, bv, dropped, pos = bucket_pack(keys, bucket, vals, r, cap,
                                       return_positions=True)
    assert int(dropped) == 2  # items 13, 14 overflow bucket 0
    pos = np.asarray(pos)
    sentinel = r * cap
    np.testing.assert_array_equal(pos, np.array([0, 1, 2, sentinel, sentinel,
                                                 sentinel, cap], np.int32))
    flat_k = np.asarray(bk).reshape(-1)
    flat_v = np.asarray(bv).reshape(-1)
    for i in range(7):
        if pos[i] < sentinel:  # inverse property: slot holds exactly this item
            assert flat_k[pos[i]] == int(keys[i])
            assert flat_v[pos[i]] == float(vals[i])


def test_bucket_pack_empty_trailing_dims():
    """A (n, 0)-shaped value leaf (scalar-per-item pytree leaf with an empty
    trailing dim) must not reach the n_buckets*capacity+1 overflow-slot
    scatter — the guard returns the empty fixed-shape buffer directly, with
    shapes/dtypes consistent with the keyed leaves and overflow still
    counted from the keys."""
    r, cap = 2, 3
    keys = jnp.asarray([10, 11, 12, 13, 14, -1, 20], jnp.int32)
    bucket = jnp.asarray([0, 0, 0, 0, 0, 0, 1], jnp.int32)
    vals = {
        "empty": jnp.zeros((7, 0), jnp.float32),
        "also_empty": jnp.zeros((7, 2, 0), jnp.int32),
        "full": jnp.arange(7, dtype=jnp.float32),
    }
    bk, bv, dropped = bucket_pack(keys, bucket, vals, r, cap)
    assert int(dropped) == 2  # overflow accounting unaffected by empty leaves
    assert bv["empty"].shape == (r, cap, 0)
    assert bv["empty"].dtype == jnp.float32
    assert bv["also_empty"].shape == (r, cap, 2, 0)
    assert bv["also_empty"].dtype == jnp.int32
    # the non-empty leaf routes exactly as it would without the empty ones
    _, bv_ref, _ = bucket_pack(keys, bucket, vals["full"], r, cap)
    np.testing.assert_array_equal(np.asarray(bv["full"]), np.asarray(bv_ref))
    # and the degenerate shape survives a jit boundary
    jitted = jax.jit(lambda k, b, v: bucket_pack(k, b, v, r, cap))
    _, bv2, d2 = jitted(keys, bucket, vals)
    assert int(d2) == 2 and bv2["empty"].shape == (r, cap, 0)


def test_bucket_pack_intra_bucket_order_stable():
    """Items of one bucket keep their input order in the packed row (the
    stable-argsort contract combiners and MoE-style positions rely on)."""
    keys = jnp.asarray([5, 3, 8, 6, 4, 7], jnp.int32)
    bucket = jnp.asarray([1, 0, 1, 0, 1, 0], jnp.int32)
    bk, _, dropped = bucket_pack(keys, bucket, jnp.zeros((6,)), 2, 4)
    assert int(dropped) == 0
    bk = np.asarray(bk)
    np.testing.assert_array_equal(bk[0], np.array([3, 6, 7, -1], np.int32))
    np.testing.assert_array_equal(bk[1], np.array([5, 8, 4, -1], np.int32))


def _bucket_pack_ref(keys, bucket, values, r, cap):
    """numpy bucket_pack with each item's bucket start searched per item."""
    n = keys.shape[0]
    b = np.where(keys >= 0, bucket, r)
    order = np.argsort(b, kind="stable")
    b_sorted = b[order]
    pos = np.arange(n) - np.searchsorted(b_sorted, b_sorted, side="left")
    in_range = (b_sorted < r) & (pos < cap)
    dest = np.where(in_range, b_sorted * cap + pos, r * cap)
    n_dropped = int(((b_sorted < r) & (pos >= cap)).sum())

    def scatter(x_sorted, fill):
        out = np.full((r * cap + 1,) + x_sorted.shape[1:], fill, x_sorted.dtype)
        out[dest[in_range]] = x_sorted[in_range]
        return out[:-1].reshape((r, cap) + x_sorted.shape[1:])

    positions = np.full((n,), r * cap, np.int32)
    positions[order] = dest
    out_values = {k: scatter(v[order], 0) for k, v in values.items()}
    return scatter(keys[order], -1), out_values, n_dropped, positions


def _bucket_pack_case(case, r, rng):
    """(keys, bucket, capacity) for one named edge case over `r` buckets."""
    n = 48
    bucket = rng.integers(0, r, n).astype(np.int32)
    keys = np.arange(n, dtype=np.int32)
    if case == "invalid":
        keys[rng.random(n) < 0.3] = -1
        return keys, bucket, n
    if case == "empty_buckets":  # only odd ids: bucket 0 and every even one empty
        ids = np.arange(r)[np.arange(r) % 2 == (r > 1)]
        return keys, rng.choice(ids, n).astype(np.int32), n
    if case == "exact_capacity":  # every bucket holds exactly `cap` items
        cap = 5
        return (np.arange(r * cap, dtype=np.int32),
                rng.permutation(np.repeat(np.arange(r, dtype=np.int32), cap)), cap)
    if case == "overflow":  # most items to bucket 0, some invalid
        bucket[rng.random(n) < 0.6] = 0
        keys[rng.random(n) < 0.2] = -1
        return keys, bucket, 3
    assert case == "all_invalid"
    return np.full((n,), -1, np.int32), bucket, 4


@pytest.mark.parametrize("return_positions", [False, True])
@pytest.mark.parametrize("r", [1, 2, 4, 8, 33])
@pytest.mark.parametrize("case", ["invalid", "empty_buckets", "exact_capacity",
                                  "overflow", "all_invalid"])
def test_bucket_pack_matches_per_item_search(case, r, return_positions):
    """Bucket windows of the sorted leaves give, bit for bit, what a search
    per item gives: keys, values, drops and positions. 1-D leaves ride the
    sort; the (n, 3) leaf is gathered by row."""
    rng = np.random.default_rng(r * 101 + len(case))
    keys, bucket, cap = _bucket_pack_case(case, r, rng)
    n = keys.shape[0]
    values = {"f": rng.standard_normal(n).astype(np.float32),
              "i": rng.integers(-9, 9, n).astype(np.int32),
              "v": rng.integers(-9, 9, (n, 3)).astype(np.int32)}
    want_k, want_v, want_dropped, want_pos = _bucket_pack_ref(keys, bucket, values, r, cap)
    packed = jax.jit(lambda k, b, v: bucket_pack(k, b, v, r, cap,
                                                 return_positions=return_positions))
    got = packed(keys, bucket, values)
    assert len(got) == (4 if return_positions else 3)
    got_k, got_v, got_dropped = got[:3]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    for name in values:
        np.testing.assert_array_equal(np.asarray(got_v[name]), want_v[name])
        assert got_v[name].dtype == want_v[name].dtype
    assert int(got_dropped) == want_dropped
    if return_positions:
        np.testing.assert_array_equal(np.asarray(got[3]), want_pos)
    if case == "exact_capacity":
        assert want_dropped == 0 and (want_k >= 0).all()
    if case == "overflow":
        assert want_dropped > 0


@pytest.mark.parametrize("r", [2, 4, 33])
def test_bucket_pack_loops_hold_no_per_item_array(r):
    """No op of bucket_pack inside a loop body has an n-element result: the
    search runs over the R+1 bucket boundaries, not once per item."""
    n = 1 << 16
    spec = jax.ShapeDtypeStruct((n,), jnp.int32)
    packed = jax.jit(lambda k, b: bucket_pack(k, b, k.astype(jnp.float32), r, n // r,
                                              return_positions=True))
    text = packed.lower(spec, spec).compile().as_text()
    scoped, in_loop = [], []
    for line in text.splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and obs.layer_of(op_name.group(1)) == obs.BUCKET_PACK:
            scoped.append(line.strip())
            if "/while/body/" in op_name.group(1):
                in_loop.append(line.strip())
    assert scoped, "no bucket_pack op in the compiled text"
    per_item = [line for line in in_loop
                if re.search(rf"[\[,]{n}[\],]", line.partition(" metadata=")[0])]
    assert not per_item, per_item[:3]


def _n_elements(shape: str) -> int:
    return int(np.prod([int(d) for d in shape.split(",") if d]))


@pytest.mark.parametrize("r", [1, 4, 33])
def test_bucket_pack_has_no_per_item_gather_or_scatter(r):
    """1-D leaves ride the sort and each bucket's row is one window: no op of
    bucket_pack scatters, and none gathers single items into an n-element
    result (only the C-wide windows and the R+1 boundary search gather)."""
    n = 1 << 16
    spec = jax.ShapeDtypeStruct((n,), jnp.int32)
    packed = jax.jit(lambda k, b: bucket_pack(
        k, b, {"f": k.astype(jnp.float32), "i": k + 1}, r, n // r))
    text = packed.lower(spec, spec).compile().as_text()
    scoped, scatters, per_item = [], [], []
    for line in text.splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not op_name or obs.layer_of(op_name.group(1)) != obs.BUCKET_PACK:
            continue
        scoped.append(line.strip())
        op = re.search(r"= (\w+)\[([\d,]*)\][^ ]* (\w[\w-]*)\(", line)
        if op is None:
            continue
        if op.group(3) == "scatter":
            scatters.append(line.strip())
        sizes = re.search(r"slice_sizes=\{([\d,]*)\}", line)
        if (op.group(3) == "gather" and sizes and sizes.group(1).split(",")[0] == "1"
                and _n_elements(op.group(2)) >= n):
            per_item.append(line.strip())
    assert scoped, "no bucket_pack op in the compiled text"
    assert not scatters, scatters[:3]
    assert not per_item, per_item[:3]


# --- wordcount ---------------------------------------------------------------


@pytest.mark.parametrize("secure", [False, True])
def test_wordcount(secure):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 50, 2000, dtype=np.int32)
    counts, dropped = wordcount(
        toks, 50, _mesh1(), secure=_secure_cfg() if secure else None
    )
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(toks, minlength=50))


# --- k-means -----------------------------------------------------------------


@pytest.mark.parametrize("secure", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_kmeans_step_matches_ref(secure, impl):
    pts, _ = generate_points(512, 8, seed=1)
    centers0 = jnp.asarray(pts[:8])
    step = make_kmeans_step(_mesh1(), secure=_secure_cfg() if secure else None, impl=impl)
    new, shift = step(jnp.asarray(pts), jnp.ones((512,), jnp.float32), centers0)
    ref, shift_ref = kmeans_step_ref(jnp.asarray(pts), centers0)
    np.testing.assert_allclose(np.asarray(new), np.asarray(ref), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(shift), float(shift_ref), rtol=1e-4)


def test_kmeans_converges_and_recovers_centers():
    pts, true_centers = generate_points(4000, 5, seed=3, spread=0.02)
    res = kmeans_fit(pts, 5, _mesh1(), max_iter=100, init="farthest")
    assert res.n_iter < 100
    # every true center has a recovered center nearby
    d = np.linalg.norm(res.centers[:, None, :] - true_centers[None], axis=-1)
    assert float(d.min(axis=0).max()) < 0.05
    # paper's termination: shift decreases below diag/1000
    assert res.center_shift[-1] < res.center_shift[0]


@pytest.mark.slow
def test_kmeans_secure_equals_plain():
    pts, _ = generate_points(1024, 6, seed=5)
    r_plain = kmeans_fit(pts, 6, _mesh1(), max_iter=20)
    r_sec = kmeans_fit(pts, 6, _mesh1(), secure=_secure_cfg(), max_iter=20)
    assert r_plain.n_iter == r_sec.n_iter
    np.testing.assert_allclose(
        np.asarray(r_plain.centers), np.asarray(r_sec.centers), rtol=1e-4, atol=1e-5
    )


# --- generic engine: mean-by-key with combiner --------------------------------


def test_engine_mean_by_key():
    rng = np.random.default_rng(7)
    n, nk = 512, 16
    keys = jnp.asarray(rng.integers(0, nk, n, dtype=np.int32))
    vals = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))

    def reduce_fn(k, v, valid):
        seg = jnp.where(valid, k, 0)
        s = jax.ops.segment_sum(jnp.where(valid, v["s"], 0.0), seg, num_segments=nk)
        c = jax.ops.segment_sum(jnp.where(valid, v["c"], 0.0), seg, num_segments=nk)
        s = jax.lax.psum(s, "data")
        c = jax.lax.psum(c, "data")
        return s / jnp.maximum(c, 1.0)

    spec = MapReduceSpec(
        map_fn=lambda k, v: (k, {"s": v, "c": jnp.ones_like(v)}),
        reduce_fn=reduce_fn,
        hash_fn=default_hash,
        capacity=n,
    )
    out, dropped = run_mapreduce(spec, keys, vals, _mesh1(), secure=_secure_cfg())
    assert int(dropped) == 0
    want = np.zeros(nk)
    cnt = np.zeros(nk)
    np.add.at(want, np.asarray(keys), np.asarray(vals))
    np.add.at(cnt, np.asarray(keys), 1)
    np.testing.assert_allclose(np.asarray(out), want / np.maximum(cnt, 1), rtol=1e-5)
