"""jit'd wrappers for the chacha20 kernel: padding, word packing, dispatch.

`impl` selects: 'pallas' (the kernel) or 'jnp' (oracle). `interpret=None`
follows the backend (`repro.kernels.resolve_interpret`): compiled on the TPU,
interpreted elsewhere.

Since the lane re-tiling, every rows-style entry point lowers onto the
(16, n_blocks) BLOCK-LANE kernel (`kernel.chacha20_xor_row_lanes`): the
wrappers here pad the block count to a lane-tile multiple, transpose into
lane layout, launch once, and slice the pad back off. Kernel-side lane pad
never reaches the caller (and therefore never reaches a shuffle wire); it
exists only so the compiled TPU lowering works on full 128-lane VREGs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.crypto import ctr as _ctr
from repro.crypto.chacha import CONSTANT_WORDS, chacha20_block_words
from repro.kernels import resolve_interpret
from repro.kernels.chacha20.kernel import (
    DEFAULT_BLOCK_LANES,
    DEFAULT_BLOCK_ROWS,
    chacha20_xor_blocks,
    chacha20_xor_row_lanes,
)


def make_state0(key_words, nonce_words, counter0) -> jax.Array:
    """Build the 16-word template state: constants | key | counter | nonce."""
    const = jnp.array(CONSTANT_WORDS, dtype=jnp.uint32)
    kw = jnp.asarray(key_words, jnp.uint32)
    nw = jnp.asarray(nonce_words, jnp.uint32)
    c = jnp.asarray(counter0, jnp.uint32).reshape(1)
    return jnp.concatenate([const, kw, c, nw])


def _lane_tile(n_blocks: int, block_lanes: int, interpret: bool) -> int:
    """Lanes per tile for a lane-layout launch (`_xor_lanes` pads to it).

    Interpret mode always takes ONE tile spanning the whole (8-aligned)
    block count: the emulator walks grid steps through a slow per-step loop
    (measured ~25x at 2 tiles vs 1), small payloads should pad to 8 blocks
    rather than burn 40x the ARX work on a 3-block wire, and the VMEM
    budget `block_lanes` protects does not bind off-accelerator. Compiled
    lowerings tile at `block_lanes` (multiple of the 128-wide VREG) and pad
    small payloads to full 128-lane multiples.
    """
    if interpret:
        return max(8, -(-n_blocks // 8) * 8)
    if n_blocks >= block_lanes:
        return block_lanes
    return max(128, -(-n_blocks // 128) * 128)


def _xor_lanes(x_blocks, state0, nonce_ids, ctr_rows, ctr_base, ctr_rowmul,
               lanes: int, interpret: bool):
    """Pad (r, n_blocks, 16) to a lane-tile multiple, launch, un-pad."""
    r, n_blocks, _ = x_blocks.shape
    pad = -(-n_blocks // lanes) * lanes - n_blocks
    if pad:
        x_blocks = jnp.concatenate(
            [x_blocks, jnp.zeros((r, pad, 16), jnp.uint32)], axis=1)
        ctr_base = jnp.concatenate([ctr_base, jnp.zeros((pad,), jnp.uint32)])
        ctr_rowmul = jnp.concatenate([ctr_rowmul, jnp.zeros((pad,), jnp.uint32)])
    y = chacha20_xor_row_lanes(
        jnp.swapaxes(x_blocks, 1, 2), state0, nonce_ids, ctr_rows,
        ctr_base, ctr_rowmul, block_lanes=lanes, interpret=interpret)
    return jnp.swapaxes(y, 1, 2)[:, :n_blocks, :]


@functools.partial(jax.jit, static_argnames=("impl", "block_rows", "interpret"))
def chacha20_xor_words(
    words: jax.Array,
    state0: jax.Array,
    *,
    impl: str = "pallas",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR a flat (n,) u32 word stream with the keystream starting at state0.

    Block i draws counter state0[12] + i. Lowers onto the BLOCK-LANE kernel
    as one single-row lane-layout launch (contiguous counters: base = iota,
    rowmul = 1) with the same `_lane_tile` policy as the shuffle wrappers —
    interpret mode takes ONE tile over the whole padded block count, so the
    flat `ctr_crypt_array` path shares both the full-lane compiled lowering
    and the fast interpret shape with the wire hot path.
    """
    interpret = resolve_interpret(interpret)
    n = words.shape[0]
    n_blocks = -(-n // 16)
    if impl == "jnp" or n_blocks == 0:
        from repro.crypto.chacha import chacha20_keystream_words

        ks = chacha20_keystream_words(state0[4:12], state0[13:16], state0[12], n)
        return words ^ ks
    lanes = _lane_tile(n_blocks, block_rows, interpret)
    x = jnp.concatenate(
        [words, jnp.zeros((n_blocks * 16 - n,), jnp.uint32)]).reshape(1, -1, 16)
    y = _xor_lanes(x, state0,
                   jnp.zeros((1,), jnp.uint32),             # nonce XOR id 0
                   state0[12:13],                           # ctr operand = counter0
                   jnp.arange(n_blocks, dtype=jnp.uint32),  # contiguous block index
                   jnp.ones((n_blocks,), jnp.uint32),
                   lanes, interpret)
    return y.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("impl", "block_lanes", "interpret"))
def chacha20_xor_rows(
    words: jax.Array,
    state0: jax.Array,
    nonce_ids: jax.Array,
    ctr_starts: jax.Array,
    *,
    impl: str = "pallas",
    block_lanes: int = DEFAULT_BLOCK_LANES,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR an (R, n_words) u32 wire buffer with per-row keystreams.

    Row i uses nonce = state0 nonce with word 0 XOR nonce_ids[i] and block
    counters starting at ctr_starts[i] (absolute — state0 word 12 is
    ignored). This is the per-leaf secure-shuffle entry point: 'pallas'
    covers the whole buffer in ONE lane-tiled launch gridded over rows ×
    lane tiles (the contiguous-counter special case of the coalesced
    kernel: base=iota, rowmul=1); 'jnp' is the bit-exact vmapped oracle
    kept for differential testing.
    """
    interpret = resolve_interpret(interpret)
    r, n = words.shape
    nonce_ids = jnp.asarray(nonce_ids, jnp.uint32)
    ctr_starts = jnp.asarray(ctr_starts, jnp.uint32)
    n_blocks = -(-n // 16)
    if impl == "jnp" or n_blocks == 0 or r == 0:
        from repro.crypto.chacha import chacha20_keystream_words

        def one(row_words, nid, ctr0):
            nonce = state0[13:16].at[0].set(state0[13] ^ nid)
            return row_words ^ chacha20_keystream_words(state0[4:12], nonce, ctr0, n)

        return jax.vmap(one)(words, nonce_ids, ctr_starts)
    lanes = _lane_tile(n_blocks, block_lanes, interpret)
    x = jnp.concatenate(
        [words, jnp.zeros((r, n_blocks * 16 - n), jnp.uint32)], axis=1
    ).reshape(r, n_blocks, 16)
    y = _xor_lanes(x, state0, nonce_ids, ctr_starts,
                   jnp.arange(n_blocks, dtype=jnp.uint32),
                   jnp.ones((n_blocks,), jnp.uint32), lanes, interpret)
    return y.reshape(r, -1)[:, :n]


@functools.partial(jax.jit, static_argnames=("impl", "block_lanes", "interpret"))
def chacha20_xor_rows_coalesced(
    words: jax.Array,
    state0: jax.Array,
    nonce_ids: jax.Array,
    ctr_rows: jax.Array,
    ctr_base: jax.Array,
    ctr_rowmul: jax.Array,
    *,
    impl: str = "pallas",
    block_lanes: int = DEFAULT_BLOCK_LANES,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR an (R, 16·n_blocks) u32 COALESCED wire with per-row keystreams.

    The coalesced secure-shuffle entry point: the whole multi-leaf wire
    (every leaf's block-aligned segment concatenated on the word axis)
    travels through ONE launch. Block j of row i draws keystream from
      nonce   = state0 nonce with word 0 XOR nonce_ids[i]
      counter = ctr_base[j] + ctr_rowmul[j] * ctr_rows[i]
    (absolute; state0 word 12 is ignored). The per-block vectors encode the
    per-leaf counter segments of `core/shuffle.py`'s layout — base carries
    leaf counter offset + intra-leaf block index, rowmul the leaf's
    blocks-per-row stride — reproducing the per-leaf path's (key, nonce,
    counter) assignment bit-for-bit. 'jnp' is the vmapped block oracle kept
    for differential testing. n_words must be a multiple of 16 (the wire is
    block-aligned by construction).
    """
    interpret = resolve_interpret(interpret)
    r, n = words.shape
    if n % 16:
        raise ValueError(f"coalesced wire must be block-aligned, got n_words={n}")
    n_blocks = n // 16
    nonce_ids = jnp.asarray(nonce_ids, jnp.uint32)
    ctr_rows = jnp.asarray(ctr_rows, jnp.uint32)
    ctr_base = jnp.asarray(ctr_base, jnp.uint32)
    ctr_rowmul = jnp.asarray(ctr_rowmul, jnp.uint32)
    if impl == "jnp" or n_blocks == 0 or r == 0:

        def one(row_words, nid, rc):
            nonce = state0[13:16].at[0].set(state0[13] ^ nid)
            counters = ctr_base + ctr_rowmul * rc
            ks = chacha20_block_words(state0[4:12], counters, nonce)
            return row_words ^ ks.reshape(-1)

        return jax.vmap(one)(words, nonce_ids, ctr_rows)
    lanes = _lane_tile(n_blocks, block_lanes, interpret)
    y = _xor_lanes(words.reshape(r, n_blocks, 16), state0, nonce_ids, ctr_rows,
                   ctr_base, ctr_rowmul, lanes, interpret)
    return y.reshape(r, -1)


def ctr_crypt_array(
    x: jax.Array,
    key_words,
    nonce_words,
    counter0=0,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
) -> jax.Array:
    """Encrypt/decrypt an arbitrary-dtype array via the kernel (XOR stream)."""
    shape, dtype = x.shape, x.dtype
    words, pad = _ctr._to_words(x)
    state0 = make_state0(key_words, nonce_words, counter0)
    out = chacha20_xor_words(words, state0, impl=impl, interpret=interpret)
    return _ctr._from_words(out, shape, dtype, pad)
