"""Keyed shuffle: fixed-shape bucketing + (optionally encrypted) all_to_all.

The paper's mappers route each (k, v) to reducer `hash(k) % rcount` and the
framework "handles all the communication aspects". On a TPU mesh the shuffle
is a single `all_to_all` over the shuffle axis; because shapes must be static,
each mapper packs its pairs into an (R, C, ...) send buffer (R = reducers on
the axis, C = per-destination capacity) exactly like MoE capacity-factor
dispatch. Overflow is counted and surfaced, never silently lost.

Secure mode encrypts the send buffer *before* the collective and decrypts
after: ciphertext is what crosses the chip boundary ("enclave exit"), exactly
the paper's trust model for the mapper→reducer network. Counter-space layout
guarantees (key, nonce, counter) uniqueness:
  nonce word 0 = base_nonce[0] XOR source_index
  nonce word 1 = base_nonce[1] XOR round_index     (iterative driver rounds)
  ctr          = ctr0 + leaf_offset + dest_row * blocks_per_row(leaf)
so the receiver of row s (sent by source s while it sat at row `my_index` of
s's buffer) can reconstruct the exact keystream without any key exchange
beyond the session key.

The round index dimension exists for `repro.core.driver`: a multi-round job
runs many shuffles under one session key, and reusing the keystream across
rounds would be a classic two-time pad. XORing the (traced) round index into
nonce word 1 gives every round a disjoint keystream while both endpoints of
the collective can still derive it locally — the round counter is part of
the shared loop state, never transmitted.

Coalesced wire layout (default)
-------------------------------
The whole pytree crosses the boundary as ONE (R, payload_words) u32 wire:
every leaf's word rows are concatenated PACKED on the word axis at STATIC
per-leaf offsets — no block-alignment pad travels — so one keystream launch
encrypts/decrypts the buffer and exactly one `lax.all_to_all` moves it, per
secure round, regardless of tree width (vs one collective per leaf and two
launches per leaf on the per-leaf path). For a 3-leaf tree
{k:(R,C) i32, s:(R,C,d) f32, c:(R,C) f32}:

    wire row i:  |<- leaf k ->|<--- leaf s --->|<- leaf c ->|
    words        [    Wk    ]  [      Ws     ]  [    Wc    ]
    word offset  0             Wk               Wk+Ws
    block ctr    c0+i·Bk+b     c0+R·Bk+i·Bs+b   c0+R·(Bk+Bs)+i·Bc+b

where W* = words_for(leaf row), B* = ceil(W*/16), b the intra-leaf block
index, and c0 = counter0. KEYSTREAM, unlike payload, is derived in the
block-ALIGNED virtual layout: one launch computes all 16·ΣB* words per row
(ctr vectors below), and each leaf's first W* words are sliced out at its
aligned offset 16·Σ preceding B* and XORed onto the packed segment. Each
leaf region therefore keeps the EXACT per-leaf (key, nonce, counter)
assignment (leaf_offset + row·blocks_per_row + b): the coalesced and
per-leaf layouts draw bit-identical keystream per leaf region — they are
cross-checkable ciphertexts, and the per-leaf path is retained as the
differential oracle (`SecureShuffleConfig.coalesce=False`). Discarded
keystream tail words (blocks whose payload ends mid-block) were derived
and discarded by the per-leaf path too, and CTR keystream words leak
nothing about other words of the same or any other block. The wire carries
ZERO pad bytes (`record_wire_bytes` reports `pad_bytes == 0`); the only
residual padding anywhere is `crypto/ctr.words_for`'s sub-word packing of
narrow dtypes inside W* itself.

Plaintext (`secure=None`) shuffles default to the SAME packed single-wire
topology minus the crypt — one `lax.all_to_all` per round, zero keystream
launches — so a secure-vs-plain jaxpr diff isolates the cryptography, not
the wire shape; `resolve_coalesce(False)` restores the historical
per-leaf collectives as the differential oracle.

The per-(row, block) counter of the coalesced wire is not a single linear
ramp, so `kernels/chacha20.chacha20_xor_rows_coalesced` takes vector
per-block counter bases: ctr[i, j] = ctr_base[j] + ctr_rowmul[j] · row_ctr[i]
with ctr_base = leaf counter offset + intra-leaf block index and ctr_rowmul
= the leaf's blocks-per-row stride.

`SecureShuffleConfig.coalesce` selects the layout: True | False | 'auto'
(the default — reads $REPRO_SHUFFLE_COALESCE, else True). Like `impl`, the
choice is read at trace time and an explicit bool always wins over the
environment.

Keystream implementation selection
----------------------------------
Two interchangeable backends compute the per-row keystream; the counter-space
layout above is IDENTICAL under both, so they are bit-exact by construction
(and proven so by `tests/test_shuffle_impls.py`):

  * ``pallas`` (default) — `repro.kernels.chacha20.chacha20_xor_rows` /
    `chacha20_xor_rows_coalesced`: the whole wire buffer in one Pallas
    launch gridded over rows × 128-wide block-LANE tiles (blocks on the
    lane dim, so the compiled TPU lowering fills every VREG lane).
    Interpret mode off-TPU keeps XLA from constant-folding the 20-round
    ARX chain, which is what made secure-mode compiles take ~40-110s per
    config on the historical path.
  * ``jnp`` — the vmapped pure-jnp ChaCha, kept as the differential-testing
    oracle.

Selection: `SecureShuffleConfig.impl` ('auto' | 'pallas' |
'pallas-interpret' | 'jnp'). 'auto' resolves to the `REPRO_CHACHA_IMPL`
environment variable when set, else 'pallas'; an explicit non-'auto' value
always wins over the environment. The choice is read at trace time — an env
flip after a runner is jitted does not retrace it. 'pallas' runs the kernel
compiled on the TPU and interpreted on any other backend
(`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.crypto import ctr as _ctr
from repro.crypto.ctr import words_for
from repro.kernels import resolve_interpret
from repro.kernels.chacha20.ops import (
    chacha20_xor_rows,
    chacha20_xor_rows_coalesced,
    make_state0,
)

CHACHA_IMPL_ENV = "REPRO_CHACHA_IMPL"
_VALID_IMPLS = ("auto", "pallas", "pallas-interpret", "jnp")

COALESCE_ENV = "REPRO_SHUFFLE_COALESCE"
_COALESCE_TRUE = ("1", "true", "yes", "on")
_COALESCE_FALSE = ("0", "false", "no", "off")


def _model_recommendation(knob: str, **ctx):
    """Ask the calibrated cost model for an `auto` knob value.

    Returns None when no calibration is active ($REPRO_CALIBRATION unset and
    nothing set via `repro.perf.model.set_active_model`), which keeps every
    `auto` resolver bit-for-bit on its historical default. Imported lazily:
    `repro.perf.model` traces programs through this module, and most resolver
    calls never need it.
    """
    from repro.perf.model import recommendation

    return recommendation(knob, **ctx)


def resolve_coalesce(coalesce="auto") -> bool:
    """Resolve a coalesce selector to a concrete bool (read at trace time).

    An explicit bool always wins; 'auto'/None defers to
    $REPRO_SHUFFLE_COALESCE, then to the calibrated cost model when one is
    active (`repro/perf/model.py`), then to the measured default True.
    Mirrors `resolve_chacha_impl`, including blaming the environment when
    its value is unparseable.
    """
    if isinstance(coalesce, (bool, np.bool_)):
        return bool(coalesce)
    if coalesce in (None, "auto"):
        env_val = os.environ.get(COALESCE_ENV)
        if env_val is None:
            rec = _model_recommendation("coalesce")
            return True if rec is None else bool(rec)
        val = env_val.strip().lower()
        if val in _COALESCE_TRUE:
            return True
        if val in _COALESCE_FALSE:
            return False
        raise ValueError(
            f"invalid ${COALESCE_ENV}={env_val!r} in the environment: "
            f"must be one of {_COALESCE_TRUE + _COALESCE_FALSE} "
            f"(unset ${COALESCE_ENV} to use the default coalesced wire)")
    raise ValueError(
        f"coalesce must be a bool or 'auto', got {coalesce!r}")


def resolve_chacha_impl(impl: str = "auto") -> tuple[str, bool]:
    """Resolve an impl selector to concrete (impl, interpret) kernel args.

    'auto' defers to $REPRO_CHACHA_IMPL, then to the calibrated cost model
    when one is active (the impl whose probed us/block wins;
    `repro/perf/model.py`), then to the measured default 'pallas'; explicit
    values win over the environment. 'pallas-interpret' forces interpret
    mode even on a backend with a compiled Pallas lowering; plain 'pallas'
    interprets only off-TPU (`repro.kernels.resolve_interpret`).
    """
    from_env = False
    if impl in (None, "auto"):
        env_val = os.environ.get(CHACHA_IMPL_ENV)
        if env_val is None:
            rec = _model_recommendation("chacha_impl")
            impl = "pallas" if rec is None else rec
        else:
            impl, from_env = env_val, True
    if impl not in _VALID_IMPLS or impl == "auto":
        if from_env:
            raise ValueError(
                f"invalid ${CHACHA_IMPL_ENV}={impl!r} in the environment: "
                f"chacha impl must be one of {_VALID_IMPLS[1:]} "
                f"(unset ${CHACHA_IMPL_ENV} to use the default 'pallas')")
        raise ValueError(
            f"chacha impl must be one of {_VALID_IMPLS[1:]}, got {impl!r}")
    if impl == "jnp":
        return "jnp", True
    if impl == "pallas-interpret":
        return "pallas", True
    return "pallas", resolve_interpret(None)


@dataclass(frozen=True)
class SecureShuffleConfig:
    """Session material for encrypting shuffle traffic (paper: k_shuffle).

    `impl` picks the keystream backend (module docstring): 'auto' (env-
    overridable, default 'pallas'), 'pallas', 'pallas-interpret', or 'jnp'.
    `coalesce` picks the wire layout (module docstring): True — the whole
    pytree as one wire buffer, one keystream launch each side of ONE
    all_to_all per round — False — the per-leaf differential oracle — or
    'auto' (env-overridable via $REPRO_SHUFFLE_COALESCE, default True).
    """

    key_words: Any  # (8,) u32
    nonce_words: Any  # (3,) u32 base nonce; word 0 is XORed with source index
    counter0: int = 0
    impl: str = "auto"
    coalesce: Any = "auto"  # bool | 'auto'

    def with_impl(self, impl: str | None) -> "SecureShuffleConfig":
        """Copy with a different keystream impl (None keeps the current one)."""
        if impl is None or impl == self.impl:
            return self
        from dataclasses import replace

        return replace(self, impl=impl)

    def with_coalesce(self, coalesce) -> "SecureShuffleConfig":
        """Copy with a different wire layout (None keeps the current one)."""
        if coalesce is None or coalesce == self.coalesce:
            return self
        from dataclasses import replace

        return replace(self, coalesce=coalesce)


def bucket_pack(keys, bucket, values, n_buckets: int, capacity: int,
                return_positions: bool = False):
    """Pack (key, value) pairs into a fixed (R, C, ...) per-destination buffer.

    Items are stably sorted by bucket in one `lax.sort` that carries the keys
    and every (n,) value leaf along, so each bucket keeps its input order. One
    search of the sorted buckets gives the R+1 bucket starts, and row j of
    every output is the length-C window of its sorted leaf at bucket j's
    start, cut to the bucket's count: contiguous copies, no per-item scatter.
    A leaf with trailing dims cannot ride the sort; an item index rides it
    instead, and the leaf's rows are gathered by that index's windows, from
    which `positions` is scattered too.

    Args:
      keys:    (n,) int32; entries with key < 0 are padding (invalid).
      bucket:  (n,) int32 destination bucket in [0, n_buckets) for each item.
      values:  pytree of arrays with leading dim n.
      capacity: per-bucket slot count C.
      return_positions: also return, per input item, its flat slot index in
        [0, R*C) (or R*C when dropped/invalid) — the inverse map used by MoE
        combine to fetch each token's expert output after the return shuffle.

    Returns:
      out_keys   (R, C) int32, -1 where empty,
      out_values pytree with leading dims (R, C),
      n_dropped  () int32 — items lost to capacity overflow
      [, positions (n,) int32].
    """
    with jax.named_scope(obs.BUCKET_PACK):
        n = keys.shape[0]
        leaves, treedef = jax.tree.flatten(values)
        b = jnp.where(keys >= 0, bucket, n_buckets)  # invalid items sort last
        flat = [i for i, v in enumerate(leaves) if v.ndim == 1]
        wide = any(v.ndim > 1 and 0 not in v.shape[1:] for v in leaves)
        index = [jnp.arange(n, dtype=jnp.int32)] if wide or return_positions else []
        b_sorted, keys_sorted, *rest = lax.sort(
            [b, keys, *(leaves[i] for i in flat), *index], num_keys=1, is_stable=True)
        sorted_flat = dict(zip(flat, rest))
        starts = jnp.searchsorted(
            b_sorted, jnp.arange(n_buckets + 1, dtype=b_sorted.dtype), side="left"
        ).astype(jnp.int32)
        counts = starts[1:] - starts[:-1]
        n_dropped = jnp.sum(jnp.maximum(counts - capacity, 0)).astype(jnp.int32)
        filled = jnp.arange(capacity, dtype=jnp.int32) < counts[:, None]

        def window(x_sorted, fill):
            # padded by C so that no bucket's window is clamped at the end
            padded = jnp.concatenate([x_sorted, jnp.full((capacity,), fill, x_sorted.dtype)])
            rows = jax.vmap(lambda s: lax.dynamic_slice_in_dim(padded, s, capacity))(starts[:-1])
            return jnp.where(filled, rows, fill)

        out_keys = window(keys_sorted, jnp.int32(-1))
        src = window(rest[-1], jnp.int32(0)) if index else None  # input item of each slot

        def pack_leaf(i, v):
            if i in sorted_flat:
                return window(sorted_flat[i], jnp.zeros((), v.dtype))
            if 0 in v.shape[1:]:  # zero-size trailing dims: nothing to place
                return jnp.zeros((n_buckets, capacity) + v.shape[1:], v.dtype)
            mask = filled.reshape(filled.shape + (1,) * (v.ndim - 1))
            return jnp.where(mask, v[src], jnp.zeros((), v.dtype))

        out_values = jax.tree.unflatten(treedef, [pack_leaf(i, v) for i, v in enumerate(leaves)])
        if not return_positions:
            return out_keys, out_values, n_dropped
        slots = jnp.arange(n_buckets * capacity, dtype=jnp.int32).reshape(n_buckets, capacity)
        positions = jnp.full((n,), n_buckets * capacity, jnp.int32).at[
            jnp.where(filled, src, n)].set(slots, mode="drop")
        return out_keys, out_values, n_dropped, positions


def _row_blocks(leaf_row_shape, dtype) -> int:
    """ChaCha blocks consumed by one (C, ...) row of an (R, C, ...) leaf."""
    return -(-words_for(leaf_row_shape, dtype) // 16)


def _round_nonce(cfg: SecureShuffleConfig, round_id):
    """Base nonce for this round: word 1 ^= round index (may be traced)."""
    base_nonce = jnp.asarray(cfg.nonce_words, jnp.uint32)
    if round_id is not None:
        r = jnp.asarray(round_id, jnp.uint32)
        base_nonce = base_nonce.at[1].set(base_nonce[1] ^ r)
    return base_nonce


def _crypt_rows(cfg: SecureShuffleConfig, words, nonce_ids, ctr_starts, round_id):
    """XOR an (R, n_words) wire buffer with the per-row keystream.

    Row i uses nonce word 0 XOR nonce_ids[i] and absolute block counter start
    ctr_starts[i]; nonce word 1 carries the round index. Dispatches to the
    backend selected by `cfg.impl` via `repro.kernels.chacha20`.
    """
    impl, interpret = resolve_chacha_impl(cfg.impl)
    state0 = make_state0(cfg.key_words, _round_nonce(cfg, round_id), 0)
    return chacha20_xor_rows(words, state0, jnp.asarray(nonce_ids, jnp.uint32),
                             jnp.asarray(ctr_starts, jnp.uint32),
                             impl=impl, interpret=interpret)


def _keystream_rows(cfg: SecureShuffleConfig, nonce_ids, ctr_rows, offset, blocks, n_words,
                    round_id=None):
    """Per-row keystream: row i uses nonce^nonce_ids[i], ctr offset+ctr_rows[i]·blocks.

    `round_id` (scalar u32, may be traced) is XORed into nonce word 1 so every
    round of an iterative job draws from a disjoint keystream. Routed through
    the impl selected by `cfg.impl` (keystream = XOR with zeros).
    """
    nonce_ids = jnp.asarray(nonce_ids, jnp.uint32)
    ctr_starts = jnp.uint32(offset) + jnp.asarray(ctr_rows, jnp.uint32) * jnp.uint32(blocks)
    zeros = jnp.zeros((nonce_ids.shape[0], n_words), jnp.uint32)
    return _crypt_rows(cfg, zeros, nonce_ids, ctr_starts, round_id)


def _pack_wire(tree):
    """Bitcast every (R, C, ...) leaf into an (R, n_words) u32 wire form.

    Ciphertext must never travel in a float dtype: XLA's bf16/f32 emulation
    may quiet NaN payloads in transit, corrupting bits. The wire format is
    opaque u32; shapes/dtypes are static metadata used to unpack.
    """
    leaves, treedef = jax.tree.flatten(tree)
    wires, meta = [], []
    for leaf in leaves:
        pad = _ctr.pad_for(leaf.shape[1:], leaf.dtype)
        words = jax.vmap(lambda row: _ctr._to_words(row)[0])(leaf)
        wires.append(words)
        meta.append((leaf.shape, leaf.dtype, pad))
    return wires, meta, treedef


def _unpack_wire(wires, meta, treedef):
    leaves = []
    for words, (shape, dtype, pad) in zip(wires, meta):
        row = jax.vmap(lambda w: _ctr._from_words(w, shape[1:], dtype, pad))(words)
        leaves.append(row)
    return jax.tree.unflatten(treedef, leaves)


def _crypt_wires(wires, meta, cfg, nonce_ids, ctr_rows, round_id=None):
    out = []
    ctr_rows = jnp.asarray(ctr_rows, jnp.uint32)
    offset = jnp.uint32(cfg.counter0)
    for words, (shape, dtype, _pad) in zip(wires, meta):
        r, n_words = words.shape
        blocks = _row_blocks(shape[1:], dtype)
        ctr_starts = offset + ctr_rows * jnp.uint32(blocks)
        with jax.named_scope(obs.KEYSTREAM):
            out.append(_crypt_rows(cfg, words, nonce_ids, ctr_starts, round_id))
        offset = offset + jnp.uint32(blocks * r)
    return out


@dataclass(frozen=True)
class _WireLayout:
    """Static unpack/counter metadata for a coalesced (R, payload_words) wire.

    leaves:      per-leaf (shape, dtype, narrow-pad, word_start, n_words,
                 blocks, ks_start) tuples — word_start is the leaf segment's
                 offset on the PACKED wire's word axis (no alignment pad);
                 ks_start = 16·Σ preceding blocks is the segment's offset in
                 the block-ALIGNED keystream layout the crypt derives.
    ctr_base:    (total_blocks,) u32 — per-block counter base: the leaf's
                 counter-space offset (Σ preceding blocks·R, matching the
                 per-leaf path) + the intra-leaf block index. cfg.counter0
                 is added at crypt time.
    ctr_rowmul:  (total_blocks,) u32 — per-block row stride: the owning
                 leaf's blocks-per-row.
    """

    leaves: tuple
    ctr_base: Any  # (total_blocks,) np.uint32
    ctr_rowmul: Any  # (total_blocks,) np.uint32
    total_blocks: int

    @property
    def total_words(self) -> int:
        """Words of the block-aligned KEYSTREAM layout (≥ payload_words)."""
        return self.total_blocks * 16

    @property
    def payload_words(self) -> int:
        """Words of the packed wire — exactly what crosses the link."""
        return sum(m[4] for m in self.leaves)


def _pack_wire_coalesced(tree):
    """Bitcast + concatenate the whole pytree into ONE packed u32 wire.

    Leaf word rows are concatenated back-to-back on the word axis at static
    offsets — leaf tails share blocks with the next leaf's head on the wire,
    so ZERO block-alignment pad travels. The counter space stays the
    block-aligned per-leaf assignment (the crypt slices each leaf's words
    out of an aligned keystream; `_WireLayout`). Returns (wire, layout,
    treedef).
    """
    leaves, treedef = jax.tree.flatten(tree)
    r = leaves[0].shape[0]
    segs, meta = [], []
    word_off = 0  # PACKED wire word offset
    ctr_off = 0  # counter-space offset: Σ preceding blocks · R
    ks_off = 0  # aligned-keystream word offset: 16 · Σ preceding blocks
    base_parts, mul_parts = [], []
    for leaf in leaves:
        pad = _ctr.pad_for(leaf.shape[1:], leaf.dtype)
        words = jax.vmap(lambda row: _ctr._to_words(row)[0])(leaf)
        n_words = words.shape[1]
        blocks = -(-n_words // 16)
        segs.append(words)
        meta.append((leaf.shape, leaf.dtype, pad, word_off, n_words, blocks, ks_off))
        base_parts.append(np.uint32(ctr_off) + np.arange(blocks, dtype=np.uint32))
        mul_parts.append(np.full((blocks,), blocks, np.uint32))
        word_off += n_words
        ctr_off += blocks * r
        ks_off += blocks * 16
    wire = (jnp.concatenate(segs, axis=1) if segs
            else jnp.zeros((r, 0), jnp.uint32))
    layout = _WireLayout(
        leaves=tuple(meta),
        ctr_base=(np.concatenate(base_parts) if base_parts
                  else np.zeros((0,), np.uint32)),
        ctr_rowmul=(np.concatenate(mul_parts) if mul_parts
                    else np.zeros((0,), np.uint32)),
        total_blocks=ks_off // 16,
    )
    return wire, layout, treedef


def _unpack_wire_coalesced(wire, layout: _WireLayout, treedef):
    leaves = []
    for shape, dtype, pad, word_start, n_words, _blocks, _ks in layout.leaves:
        words = lax.slice_in_dim(wire, word_start, word_start + n_words, axis=1)
        leaves.append(
            jax.vmap(lambda w: _ctr._from_words(w, shape[1:], dtype, pad))(words))
    return jax.tree.unflatten(treedef, leaves)


def _packed_keystream(ks_aligned, layout: _WireLayout):
    """Slice the packed wire's keystream out of the block-aligned keystream.

    `ks_aligned` is (R, 16·total_blocks): each leaf's first n_words at its
    aligned ks_start offset, concatenated, give the (R, payload_words)
    keystream whose XOR with the packed wire reproduces the per-leaf
    ciphertext bit-for-bit; the skipped tail words are discarded exactly as
    the per-leaf path discards them.
    """
    segs = [lax.slice_in_dim(ks_aligned, m[6], m[6] + m[4], axis=1)
            for m in layout.leaves]
    return jnp.concatenate(segs, axis=1) if segs else ks_aligned[:, :0]


def _crypt_wire_coalesced(wire, layout: _WireLayout, cfg, nonce_ids, ctr_rows,
                          round_id=None):
    """XOR the packed coalesced wire with its keystream — ONE launch.

    The keystream is derived in the block-aligned layout (XOR with zeros):
    block j of row i uses counter counter0 + ctr_base[j] + ctr_rowmul[j] ·
    ctr_rows[i] and nonce word 0 XOR nonce_ids[i] — bit-identical per leaf
    region to what `_crypt_wires` derives on the per-leaf path — then each
    leaf's payload words are sliced out (`_packed_keystream`) and XORed onto
    the packed wire, so no pad words travel.
    """
    if layout.total_blocks == 0:
        return wire
    with jax.named_scope(obs.KEYSTREAM):
        nonce_ids = jnp.asarray(nonce_ids, jnp.uint32)
        ctr_rows = jnp.asarray(ctr_rows, jnp.uint32)
        ctr_base = jnp.uint32(cfg.counter0) + jnp.asarray(layout.ctr_base, jnp.uint32)
        ctr_rowmul = jnp.asarray(layout.ctr_rowmul, jnp.uint32)
        zeros = jnp.zeros((wire.shape[0], layout.total_words), jnp.uint32)
        impl, interpret = resolve_chacha_impl(cfg.impl)
        state0 = make_state0(cfg.key_words, _round_nonce(cfg, round_id), 0)
        ks = chacha20_xor_rows_coalesced(zeros, state0, nonce_ids, ctr_rows,
                                         ctr_base, ctr_rowmul,
                                         impl=impl, interpret=interpret)
        return wire ^ _packed_keystream(ks, layout)


class _WireAccounting:
    """Trace-time shuffle byte counter (see `record_wire_bytes`).

    Re-entrant by construction: active `record_wire_bytes` contexts form a
    STACK of independent record sinks (every traced shuffle appends to all
    of them), suppression is a nesting counter, and the job attribution of
    a record comes from the innermost `tagged(job_id)` context — so two
    interleaved `run_until` jobs (the serving path: chunk dispatches of
    concurrent jobs alternate on one host thread, each holding its own
    open recording context across its generator's suspensions) neither
    clobber each other's record lists nor mis-attribute records. Sinks are
    removed by IDENTITY on context exit, so out-of-LIFO-order exits — the
    norm for generator-held contexts — are safe.
    """

    def __init__(self):
        self._sinks: list[list] = []
        self._tags: list = []
        self._suppress = 0

    @property
    def enabled(self) -> bool:
        return bool(self._sinks) and self._suppress == 0

    def note(self, *, secure: bool, nbytes: int, n_leaves: int, halted: bool = False,
             coalesced: bool = False, pad_bytes: int = 0,
             per_leaf: list | None = None, collectives: int = 0,
             keystream_launches: int = 0, keystream_blocks: int = 0):
        """Append one record per traced `keyed_all_to_all` to every sink.

        bytes:              payload bytes — raw leaf bytes in plaintext
                            mode, packed u32 payload words in secure mode;
                            the quantity `bench_data_volume` compares to
                            prove zero CTR ciphertext expansion.
        wire_bytes:         bytes actually crossing the inter-chip link =
                            bytes + pad_bytes (the coalesced wire's ≤15-word
                            per-leaf block-alignment pad; 0 otherwise).
        per_leaf:           per-leaf payload byte breakdown, in pytree leaf
                            order, so the zero-expansion claim is auditable
                            LEAF BY LEAF even when the wire is coalesced.
        collectives:        all_to_all ops this shuffle traces per round.
        keystream_launches: keystream derivations (encrypt + decrypt) this
                            shuffle traces per round; 0 in plaintext mode.
        keystream_blocks:   total ChaCha20 blocks derived per round, summed
                            across launches (UNPADDED — kernel lane-tile
                            padding is an impl detail the cost model applies
                            itself); 0 in plaintext mode.
        job:                innermost `tagged` job id, or None — lets a
                            shared sink split interleaved jobs' records.
        """
        if not self.enabled:
            return
        rec = {"secure": secure, "bytes": nbytes, "leaves": n_leaves,
               "halted": halted, "coalesced": coalesced,
               "wire_bytes": nbytes + pad_bytes, "pad_bytes": pad_bytes,
               "per_leaf": list(per_leaf or []), "collectives": collectives,
               "keystream_launches": keystream_launches,
               "keystream_blocks": keystream_blocks,
               "job": self._tags[-1] if self._tags else None}
        for sink in self._sinks:
            sink.append(dict(rec))

    def note_halted_round(self, secure: bool = True):
        """Record the halted-round passthrough: ZERO bytes cross the wire.

        Called while tracing the skip branch of the driver's halt-masked
        round loop — the branch contains no all_to_all and no keystream
        derivation, so the bytes a halted round contributes are zero by
        construction, and the record makes that auditable from benchmarks.
        """
        self.note(secure=secure, nbytes=0, n_leaves=0, halted=True)

    @contextmanager
    def suppressed(self):
        """Context: disable recording (abstract eval_shape passes would
        otherwise double-count a shuffle the driver only traces for shapes).
        Nestable — a counter, not a flag, so an inner suppression cannot
        un-suppress an outer one."""
        self._suppress += 1
        try:
            yield
        finally:
            self._suppress -= 1

    @contextmanager
    def tagged(self, job_id):
        """Context: attribute records traced inside to `job_id`.

        The driver wraps each chunk dispatch of a tagged job in this, so a
        sink shared by interleaved jobs can be split by the records' "job"
        field. None is a no-op (records keep the enclosing tag, if any).
        """
        if job_id is None:
            yield
            return
        self._tags.append(job_id)
        try:
            yield
        finally:
            self._tags.remove(job_id)


wire_accounting = _WireAccounting()


class record_wire_bytes:
    """Context manager: record per-shuffle wire bytes at TRACE time.

    Every `keyed_all_to_all` traced inside the block appends one record with
    the exact byte count that crosses the inter-chip link per shard — raw
    leaf bytes in plaintext mode, packed u32 wire words in secure mode.
    Shapes are static, so trace-time accounting is exact; a shuffle inside
    `lax.scan` (the iterative driver) traces once and records ONE round's
    bytes. Used by `benchmarks/bench_data_volume.py` to prove CTR ciphertext
    expansion is zero.

    RE-ENTRANT: contexts nest (each gets its own record list; a shuffle
    traced under several open contexts lands in all of them) and may exit
    in any order — each `__exit__` removes only its own sink — so
    interleaved `run_until` jobs that each hold a context open across
    host-dispatch turns cannot corrupt one another's accounting. Records
    carry a "job" field from the innermost `wire_accounting.tagged(job_id)`
    context (None untagged) to split a shared sink by job.
    """

    def __init__(self):
        self.records: list[dict] = []

    def __enter__(self):
        self.records = []
        wire_accounting._sinks.append(self.records)
        return self.records

    def __exit__(self, *exc):
        # remove by IDENTITY, wherever it sits: interleaved contexts exit
        # out of stack order
        for i, sink in enumerate(wire_accounting._sinks):
            if sink is self.records:
                del wire_accounting._sinks[i]
                break
        return False


def keyed_all_to_all(tree, axis_name: str, secure: SecureShuffleConfig | None = None,
                     round_index=None, coalesce=None):
    """all_to_all every (R, C, ...) leaf; row i of the result came from source i.

    In secure mode leaves are packed to u32 wire words, encrypted, exchanged,
    decrypted, and unpacked — only ciphertext crosses the inter-chip link.
    With the default coalesced layout (`secure.coalesce`, module docstring)
    the whole pytree travels as ONE packed wire buffer (zero pad bytes): one
    keystream launch each side of exactly one `lax.all_to_all`, regardless
    of tree width; the per-leaf layout (one collective and two launches per
    leaf) is kept as the differential oracle. Plaintext mode uses the SAME
    wire topology minus the crypt, selected by `coalesce` (True | False |
    None → 'auto', i.e. $REPRO_SHUFFLE_COALESCE, default True; in secure
    mode the config's own `secure.coalesce` governs and `coalesce` is
    ignored). `round_index` (scalar, may be traced — e.g. a `lax.scan`
    carry from the iterative driver) selects a disjoint keystream per
    round; None is equivalent to round 0.
    """
    if secure is None:
        leaves = jax.tree.leaves(tree)
        raw_bytes = [l.size * l.dtype.itemsize for l in leaves]
        if resolve_coalesce("auto" if coalesce is None else coalesce):
            wire, layout, treedef = _pack_wire_coalesced(tree)
            r = wire.shape[0]
            wire_accounting.note(
                secure=False,
                nbytes=layout.payload_words * r * 4,
                n_leaves=len(layout.leaves),
                coalesced=True,
                pad_bytes=0,
                per_leaf=[m[4] * r * 4 for m in layout.leaves],
                collectives=1,
            )
            with jax.named_scope(obs.EXCHANGE):
                wire = lax.all_to_all(wire, axis_name, 0, 0, tiled=True)
            return _unpack_wire_coalesced(wire, layout, treedef)
        wire_accounting.note(
            secure=False,
            nbytes=sum(raw_bytes),
            n_leaves=len(leaves),
            per_leaf=raw_bytes,
            collectives=len(leaves),
        )
        with jax.named_scope(obs.EXCHANGE):
            return jax.tree.map(lambda x: lax.all_to_all(x, axis_name, 0, 0, tiled=True), tree)

    r = jax.tree.leaves(tree)[0].shape[0]
    idx = lax.axis_index(axis_name).astype(jnp.uint32)

    # sender: nonce <- XOR my index; counter row <- destination row
    my_id = jnp.broadcast_to(idx, (r,))
    dest_rows = jnp.arange(r, dtype=jnp.uint32)
    # receiver: row s came from source s; at the source it sat at row my_idx
    src_ids = jnp.arange(r, dtype=jnp.uint32)
    my_rows = jnp.broadcast_to(idx, (r,))

    if resolve_coalesce(secure.coalesce):
        wire, layout, treedef = _pack_wire_coalesced(tree)
        per_leaf = [m[4] * r * 4 for m in layout.leaves]
        wire_accounting.note(
            secure=True,
            nbytes=sum(per_leaf),
            n_leaves=len(layout.leaves),
            coalesced=True,
            pad_bytes=wire.shape[1] * r * 4 - sum(per_leaf),  # 0: packed wire
            per_leaf=per_leaf,
            collectives=1,
            keystream_launches=2,
            keystream_blocks=2 * r * layout.total_blocks,
        )
        wire = _crypt_wire_coalesced(wire, layout, secure, my_id, dest_rows,
                                     round_index)
        with jax.named_scope(obs.EXCHANGE):
            wire = lax.all_to_all(wire, axis_name, 0, 0, tiled=True)
        wire = _crypt_wire_coalesced(wire, layout, secure, src_ids, my_rows,
                                     round_index)
        return _unpack_wire_coalesced(wire, layout, treedef)

    wires, meta, treedef = _pack_wire(tree)
    wire_accounting.note(
        secure=True,
        nbytes=sum(w.size * 4 for w in wires),
        n_leaves=len(wires),
        per_leaf=[w.size * 4 for w in wires],
        collectives=len(wires),
        keystream_launches=2 * len(wires),
        keystream_blocks=2 * sum(w.shape[0] * -(-w.shape[1] // 16) for w in wires),
    )

    wires = _crypt_wires(wires, meta, secure, my_id, dest_rows, round_index)

    with jax.named_scope(obs.EXCHANGE):
        wires = [lax.all_to_all(w, axis_name, 0, 0, tiled=True) for w in wires]

    wires = _crypt_wires(wires, meta, secure, src_ids, my_rows, round_index)
    return _unpack_wire(wires, meta, treedef)
