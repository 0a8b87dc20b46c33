"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler refuses what interpret mode accepts (block shapes the
layout does not allow, scalars outside SMEM, more fast memory than a kernel
may use), so these tests compile the two Pallas kernels at the widths the
service drives, and one secure k-means chunk program, for a v5e that is
described rather than attached. Nothing runs. The topology is described
inside a fixture: only one process at a time may load the TPU library, and
a test worker that never reaches these tests must not take it.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30
K, D = 10, 2          # the paper's k-means (§V)
SORT_N = 1 << 24      # values of one secure sort job on one chip
KMEANS_N = 1 << 24    # points of one secure k-means job on one chip
KMEANS_KERNEL_N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _wire_layout(tree):
    """The coalesced wire layout of a shuffle tree of shapes."""
    from repro.core import shuffle

    box = {}

    def pack(t):
        wire, box["layout"], _ = shuffle._pack_wire_coalesced(t)
        return wire

    jax.eval_shape(pack, tree)
    return box["layout"]


def _assert_kernel_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return mem


@pytest.mark.parametrize("wire", ["kmeans", "sort"])
def test_chacha_keystream_kernel_compiles_for_v5e(one_chip, wire):
    from repro.kernels.chacha20.ops import chacha20_xor_rows_coalesced

    if wire == "kmeans":  # one chip: R=1 row of k (center, partials) pairs
        tree = {"k": jax.ShapeDtypeStruct((1, K), jnp.int32),
                "v": {"s": jax.ShapeDtypeStruct((1, K, D), jnp.float32),
                      "c": jax.ShapeDtypeStruct((1, K), jnp.float32)}}
    else:  # one chip: the whole input crosses as (bucket, value) pairs
        tree = {"k": jax.ShapeDtypeStruct((1, SORT_N), jnp.int32),
                "v": {"v": jax.ShapeDtypeStruct((1, SORT_N), jnp.float32)}}
    layout = _wire_layout(tree)
    blocks = layout.total_blocks
    u32 = jnp.uint32
    fn = jax.jit(lambda w, s0, nid, rc, base, mul: chacha20_xor_rows_coalesced(
        w, s0, nid, rc, base, mul, interpret=False))
    compiled = fn.lower(
        _sds((1, 16 * blocks), u32, one_chip), _sds((16,), u32, one_chip),
        _sds((1,), u32, one_chip), _sds((1,), u32, one_chip),
        _sds((blocks,), u32, one_chip), _sds((blocks,), u32, one_chip)).compile()
    _assert_kernel_compiled(compiled)


def test_kmeans_assign_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.kmeans.ops import kmeans_assign

    fn = jax.jit(lambda p, c, w: kmeans_assign(p, c, w, impl="pallas",
                                               interpret=False))
    compiled = fn.lower(_sds((KMEANS_KERNEL_N, D), jnp.float32, one_chip),
                        _sds((K, D), jnp.float32, one_chip),
                        _sds((KMEANS_KERNEL_N,), jnp.float32, one_chip)).compile()
    _assert_kernel_compiled(compiled)


def test_secure_kmeans_chunk_compiles_for_v5e(topo, monkeypatch):
    """One fused secure k-means chunk as the service builds it, on a
    one-device mesh of the described chip, with the compiled keystream."""
    from dataclasses import replace

    from repro.compat import make_mesh
    from repro.core.driver import make_iterative_runner
    from repro.core.kmeans import make_kmeans_iterative_spec
    from repro.core.shuffle import SecureShuffleConfig
    from repro.crypto import chacha

    # the kernels pick compiled or interpreted from the default backend,
    # which is the CPU here; the program is built for the described TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((1,), ("data",), devices=topo.devices[:1])
    split, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    secure = SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x01" * 12))
    spec = make_kmeans_iterative_spec(K, 1, runtime_threshold=True)
    runner = make_iterative_runner(replace(spec, n_rounds=2), mesh,
                                   secure=secure, donate_state=True)
    compiled = runner.jitted.lower(
        {"p": _sds((KMEANS_N, D), jnp.float32, split),
         "w": _sds((KMEANS_N,), jnp.float32, split)},
        {"c": _sds((K, D), jnp.float32, rep), "thr": _sds((), jnp.float32, rep)},
        _sds((), jnp.uint32, rep)).compile()
    mem = _assert_kernel_compiled(compiled)
    assert mem.argument_size_in_bytes >= KMEANS_N * (D + 1) * np.float32().nbytes
