"""Pallas TPU kernel: ChaCha20-CTR keystream generation fused with XOR.

One ARX core (`_keystream_tile`), one data layout:

  * BLOCK-LANE layout — (16, n_blocks) u32: word index on the sublane dim,
    BLOCKS on the 128-wide lane dim. Every entry point lowers onto this
    kernel (`chacha20_xor_row_lanes`): the 16 state words live as (1, L)
    vectors, so every quarter-round step is an L-lane vector op and the
    compiled TPU lowering uses all 128 lanes of each VREG instead of the
    16/128 the historical block-row layout filled (the 7/8-waste the
    ROADMAP named). The per-(row, block) counter is
    `ctr_base[j] + ctr_rowmul[j] * row_ctr` — vector per-block bases, which
    is what lets one launch cover a wire buffer whose blocks belong to
    differently-strided per-leaf counter segments (the coalesced secure
    shuffle).
  * The BLOCK-ROW call surfaces — (n_blocks, 16) single-stream
    `chacha20_xor_blocks` (the `ctr_crypt_array` path) and (R, n_blocks, 16)
    batched `chacha20_xor_row_blocks` — are thin transposing wrappers over
    the lane kernel: block counters become the contiguous special case
    (base = iota, rowmul = 1), so the flat path gets the same full-lane
    utilization as the shuffle hot path and the keystreams cannot drift.

TPU mapping notes:
  * ARX only: add / xor / rotl on u32 — pure VPU lanework, MXU idle; the
    20 rounds are unrolled (no loop-carried scalars).
  * Lane tile = (16, L) u32 = 64·L bytes. Default L=2048 → 128 KiB in +
    128 KiB out per tile, comfortably inside 16 MiB VMEM while long enough
    to amortize control overhead.
  * Counters are derived in-kernel from per-tile base/rowmul vectors:
    the keystream never round-trips through HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.crypto.chacha import _QR_SCHEDULE
from repro.kernels import resolve_interpret

DEFAULT_BLOCK_ROWS = 2048
# blocks per lane tile of the (16, n_blocks) layout; multiple of the 128-lane
# VREG width so the compiled TPU lowering is fully lane-aligned
DEFAULT_BLOCK_LANES = 2048


def _keystream_tile(init):
    """20 unrolled ARX rounds + feed-forward over 16 state vectors.

    The one cryptographic core of every entry point (they all lower onto
    the block-lane kernel), so their keystreams cannot diverge. `init` is 16
    (1, L) vectors — one per state word, blocks on the lanes; returns the 16
    keystream words as (1, L) vectors.
    """

    def rotl(v, n):
        return (v << n) | (v >> (32 - n))

    xs = list(init)
    for _ in range(10):
        for a, b, c, d in _QR_SCHEDULE:
            xa, xb, xc, xd = xs[a], xs[b], xs[c], xs[d]
            xa = xa + xb
            xd = rotl(xd ^ xa, 16)
            xc = xc + xd
            xb = rotl(xb ^ xc, 12)
            xa = xa + xb
            xd = rotl(xd ^ xa, 8)
            xc = xc + xd
            xb = rotl(xb ^ xc, 7)
            xs[a], xs[b], xs[c], xs[d] = xa, xb, xc, xd

    return [x + x0 for x, x0 in zip(xs, init)]


def chacha20_xor_blocks(
    x_blocks: jax.Array,
    state0: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR a (n_blocks, 16) u32 message with the keystream.

    `state0` is the 16-word template state (constants, key, counter0, nonce);
    row i uses block counter state0[12] + i. n_blocks must be a multiple of
    block_rows (ops.py pads).

    Since the lane re-tiling this is a thin wrapper over the BLOCK-LANE
    kernel: the message transposes into one (1, 16, n_blocks) lane-layout
    row whose per-block counters are the contiguous special case
    `state0[12] + iota` (nonce id 0 leaves the template nonce untouched), so
    the flat single-stream path — `ctr_crypt_array` via
    `ops.chacha20_xor_words` — runs at full 128-lane VREG utilization
    instead of the 16/128 the retired block-row grid filled.
    """
    n_blocks = x_blocks.shape[0]
    assert x_blocks.shape[1] == 16 and x_blocks.dtype == jnp.uint32
    assert n_blocks % block_rows == 0, (n_blocks, block_rows)
    y = chacha20_xor_row_lanes(
        jnp.swapaxes(x_blocks, 0, 1)[None],       # (1, 16, n_blocks)
        state0,
        jnp.zeros((1,), jnp.uint32),              # nonce XOR id 0
        state0[12:13],                            # per-row ctr operand = counter0
        jnp.arange(n_blocks, dtype=jnp.uint32),   # intra-stream block index
        jnp.ones((n_blocks,), jnp.uint32),        # contiguous stride
        block_lanes=block_rows,
        interpret=interpret,
    )
    return jnp.swapaxes(y[0], 0, 1)


def _chacha20_lanes_tile_kernel(state0_ref, nid_ref, row_ref, base_ref,
                                mul_ref, x_ref, y_ref, *, block_lanes: int):
    """One (row, lane-tile) program of the batched multi-row stream.

    The grid is (n_rows, n_blocks // block_lanes): program (i, j) encrypts
    lane-layout blocks [j*L, (j+1)*L) of wire row i, where the data tile is
    (1, 16, L) — word index on the sublane dim, blocks on the lane dim. The
    scalars (template state, per-row nonce id and counter operand) are read
    from SMEM; the row's nonce is the template nonce with word 0 XOR
    nid_ref[i], and the block counter of lane l is
    `base_ref[0, l] + mul_ref[0, l] * row_ref[i]` (absolute — state0 word 12
    is ignored), so one launch covers blocks whose counters advance with
    different per-segment strides (the coalesced wire). Each of the 16
    keystream words is a full (1, L) lane vector; they stack on the sublane
    axis into the (16, L) tile, which is stored once (sixteen separate row
    stores made the interpreted CPU program take minutes to compile).
    """
    i = pl.program_id(0)
    ctr = base_ref[...] + mul_ref[...] * row_ref[i]  # (1, L)

    def splat(word):
        return jnp.full((1, block_lanes), word, jnp.uint32)

    init = [splat(state0_ref[w]) for w in range(12)]
    init += [ctr, splat(state0_ref[13] ^ nid_ref[i]),
             splat(state0_ref[14]), splat(state0_ref[15])]
    y_ref[0] = x_ref[0] ^ jnp.concatenate(_keystream_tile(init), axis=0)


def chacha20_xor_row_lanes(
    x_lanes: jax.Array,
    state0: jax.Array,
    nonce_ids: jax.Array,
    ctr_rows: jax.Array,
    ctr_base: jax.Array,
    ctr_rowmul: jax.Array,
    *,
    block_lanes: int = DEFAULT_BLOCK_LANES,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR an (n_rows, 16, n_blocks) u32 lane-layout buffer with keystream.

    One launch covers the whole buffer with a (rows × lane-tiles) grid —
    the secure-shuffle fast path. Row i, block j draws keystream from
      nonce   = state0 nonce with word 0 XOR nonce_ids[i]
      counter = ctr_base[j] + ctr_rowmul[j] * ctr_rows[i]
    (absolute; state0[12] is ignored). The vector bases let one launch span
    a coalesced multi-leaf wire: within leaf segment l, ctr_base carries the
    leaf's counter offset + intra-leaf block index and ctr_rowmul the leaf's
    blocks-per-row stride. n_blocks must be a multiple of block_lanes
    (ops.py pads); the legacy contiguous layout is base=iota, rowmul=1,
    ctr_rows=per-row starts. `interpret` None follows the backend
    (`repro.kernels.resolve_interpret`).

    state0, nonce_ids and ctr_rows sit whole in SMEM (they are read as
    scalars); the per-block counter vectors travel as lane-dense (1, L)
    blocks of a (1, n_blocks) view.
    """
    n_rows, w, n_blocks = x_lanes.shape
    assert w == 16 and x_lanes.dtype == jnp.uint32
    assert n_blocks % block_lanes == 0, (n_blocks, block_lanes)
    assert nonce_ids.shape == (n_rows,) and ctr_rows.shape == (n_rows,)
    assert ctr_base.shape == (n_blocks,) and ctr_rowmul.shape == (n_blocks,)
    grid = (n_rows, n_blocks // block_lanes)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lane_vec = pl.BlockSpec((1, block_lanes), lambda i, j: (0, j))
    tile = pl.BlockSpec((1, 16, block_lanes), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_chacha20_lanes_tile_kernel, block_lanes=block_lanes),
        grid=grid,
        in_specs=[smem, smem, smem, lane_vec, lane_vec, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_rows, 16, n_blocks), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(state0, nonce_ids, ctr_rows, ctr_base.reshape(1, n_blocks),
      ctr_rowmul.reshape(1, n_blocks), x_lanes)


def chacha20_xor_row_blocks(
    x_rows: jax.Array,
    state0: jax.Array,
    nonce_ids: jax.Array,
    ctr_starts: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """XOR an (n_rows, n_blocks, 16) u32 buffer with per-row keystreams.

    Legacy block-row interface kept for the per-leaf differential oracle and
    the kernel test suite; since the lane re-tiling it is a thin wrapper
    that transposes into the (rows, 16, blocks) lane layout and runs the
    SAME `chacha20_xor_row_lanes` kernel with the contiguous-counter
    special case (base=iota, rowmul=1), so the two entry points cannot
    drift. Row i, block j draws keystream from
      nonce  = state0 nonce with word 0 XOR nonce_ids[i]
      counter = ctr_starts[i] + j       (absolute; state0[12] is ignored)
    n_blocks must be a multiple of block_rows (ops.py pads).
    """
    n_rows, n_blocks, w = x_rows.shape
    assert w == 16 and x_rows.dtype == jnp.uint32
    assert n_blocks % block_rows == 0, (n_blocks, block_rows)
    assert nonce_ids.shape == (n_rows,) and ctr_starts.shape == (n_rows,)
    y = chacha20_xor_row_lanes(
        jnp.swapaxes(x_rows, 1, 2),
        state0,
        nonce_ids,
        ctr_starts,
        jnp.arange(n_blocks, dtype=jnp.uint32),
        jnp.ones((n_blocks,), jnp.uint32),
        block_lanes=block_rows,
        interpret=interpret,
    )
    return jnp.swapaxes(y, 1, 2)
