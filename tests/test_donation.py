"""Dispatch-side buffer donation for the fused-round runners.

`run_until` re-dispatches the carried state every chunk; with
`donate_state` (its default) the state argument is donated to the jitted
dispatch so XLA writes the chunk's output state into the input's storage
instead of allocating a fresh replica per dispatch. These tests assert the
no-copy contract at both levels: the lowering carries the input→output
aliasing annotation, and at runtime the donated buffer is actually consumed
(deleted) — while `run_until` still shields the CALLER's init_state with
its single up-front defensive copy.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core.driver import IterativeSpec, make_iterative_runner, run_until
from repro.core.engine import identity_hash


def _counting_spec(halt_at: float | None = None) -> IterativeSpec:
    """Tiny 1-shard job: state is a running per-key sum (replicated)."""

    def map_fn(state, inputs, r):
        return inputs["k"], {"v": inputs["v"]}

    def reduce_fn(state, rk, rv, valid, r):
        seg = jnp.where(valid, rk, 0)
        add = jax.ops.segment_sum(jnp.where(valid, rv["v"], 0.0), seg,
                                  num_segments=state.shape[0])
        new_state = jax.lax.psum(add, "data") + state
        return new_state, {"total": jnp.sum(new_state)}

    halt_fn = None
    if halt_at is not None:
        def halt_fn(state, aux, r):
            return aux["total"] >= halt_at

    return IterativeSpec(map_fn=map_fn, reduce_fn=reduce_fn,
                         hash_fn=identity_hash, capacity=4, n_rounds=2,
                         halt_fn=halt_fn)


def _inputs():
    return {"k": jnp.asarray([0, 1, 2, 3], jnp.int32),
            "v": jnp.ones((4,), jnp.float32)}


def test_donating_runner_lowering_aliases_state():
    """The donated state arg must appear as an input/output alias in the
    lowered program — the trace-level proof that no copy is emitted."""
    mesh = make_mesh((1,), ("data",))
    spec = _counting_spec()
    inputs, state = _inputs(), jnp.zeros((4,), jnp.float32)
    donating = make_iterative_runner(spec, mesh, donate_state=True)
    plain = make_iterative_runner(spec, mesh, donate_state=False)
    txt = donating.jitted.lower(inputs, state, jnp.uint32(0)).as_text()
    assert "tf.aliasing_output" in txt
    txt_plain = plain.jitted.lower(inputs, state, jnp.uint32(0)).as_text()
    assert "tf.aliasing_output" not in txt_plain


def test_donating_runner_consumes_state_not_inputs():
    """Runtime proof of no-copy: the donated state buffer is DELETED by the
    dispatch (its storage was reused for the output), while the sharded
    inputs — reused across every chunk — survive untouched."""
    mesh = make_mesh((1,), ("data",))
    spec = _counting_spec()
    inputs = _inputs()
    runner = make_iterative_runner(spec, mesh, donate_state=True)
    state = jnp.zeros((4,), jnp.float32)
    out_state, aux, dropped = runner(inputs, state, 0)
    assert state.is_deleted(), "donated state arg must be consumed, not copied"
    assert not inputs["k"].is_deleted() and not inputs["v"].is_deleted()
    # chunk-loop shape: feeding the output back re-donates cleanly. (Do NOT
    # np.asarray(out_state) first — materializing the host value caches it
    # on the Array and masks the deletion flag this test reads.)
    out2, _, _ = runner(inputs, out_state, 2)
    assert out_state.is_deleted()
    np.testing.assert_array_equal(np.asarray(out2), np.full((4,), 4.0, np.float32))


def _sharded_state_spec(halt_at: float | None = None) -> IterativeSpec:
    """Mixed-tier state: 'big' is a resident P(axis) leaf, 'tot' replicated.
    Donation must alias BOTH — sharded leaves stay resident on their devices
    with zero copies between chunks (module docstring: DONATION)."""

    def map_fn(state, inputs, r):
        return inputs["k"], {"v": inputs["v"]}

    def reduce_fn(state, rk, rv, valid, r):
        got = jax.lax.psum(jnp.sum(jnp.where(valid, rv["v"], 0.0)), "data")
        return ({"big": state["big"] + got, "tot": state["tot"] + got},
                {"total": state["tot"] + got})

    halt_fn = None
    if halt_at is not None:
        def halt_fn(state, aux, r):
            return aux["total"] >= halt_at

    return IterativeSpec(map_fn=map_fn, reduce_fn=reduce_fn,
                         hash_fn=identity_hash, capacity=4, n_rounds=2,
                         halt_fn=halt_fn,
                         state_specs={"big": P("data"), "tot": P()})


def _sharded_state():
    return {"big": jnp.zeros((1, 8), jnp.float32), "tot": jnp.float32(0.0)}


def test_donation_consumes_sharded_state_leaves():
    """Donation is layout-agnostic: a P(axis) carried leaf is aliased in the
    lowering and consumed at runtime exactly like a replicated one."""
    mesh = make_mesh((1,), ("data",))
    spec = _sharded_state_spec()
    inputs = _inputs()
    runner = make_iterative_runner(spec, mesh, donate_state=True)
    state = _sharded_state()
    txt = runner.jitted.lower(inputs, state, jnp.uint32(0)).as_text()
    assert txt.count("tf.aliasing_output") >= 2  # both leaves aliased
    out_state, aux, dropped = runner(inputs, state, 0)
    assert state["big"].is_deleted() and state["tot"].is_deleted()
    # chunk-loop shape: the output re-donates cleanly, sharded leaf included
    out2, _, _ = runner(inputs, out_state, 2)
    assert out_state["big"].is_deleted() and out_state["tot"].is_deleted()
    np.testing.assert_array_equal(np.asarray(out2["big"]),
                                  np.full((1, 8), 16.0, np.float32))


def test_run_until_donates_sharded_state_but_preserves_callers():
    """run_until's chunk loop with a sharded leaf: the caller's init_state
    survives, and donating matches the non-donating path bit for bit."""
    mesh = make_mesh((1,), ("data",))
    spec = _sharded_state_spec(halt_at=7.5)
    inputs = _inputs()
    init = _sharded_state()
    res = run_until(spec, inputs, init, mesh, max_rounds=8, min_chunk=1)
    assert not init["big"].is_deleted() and not init["tot"].is_deleted()
    assert res.halted and res.rounds_executed == 2
    ref = run_until(spec, inputs, init, mesh, max_rounds=8, min_chunk=1,
                    donate_state=False)
    np.testing.assert_array_equal(np.asarray(res.state["big"]),
                                  np.asarray(ref.state["big"]))
    np.testing.assert_array_equal(np.asarray(res.state["tot"]),
                                  np.asarray(ref.state["tot"]))


def test_run_until_donates_but_preserves_callers_state():
    """run_until donates every chunk's state internally (one defensive copy
    up front) — the caller's init_state must remain live and unchanged, and
    results must match the non-donating path bit for bit."""
    mesh = make_mesh((1,), ("data",))
    spec = _counting_spec(halt_at=7.5)
    inputs = _inputs()
    init = jnp.zeros((4,), jnp.float32)
    res = run_until(spec, inputs, init, mesh, max_rounds=8, min_chunk=1)
    assert not init.is_deleted()
    np.testing.assert_array_equal(np.asarray(init), np.zeros((4,), np.float32))
    assert res.halted and res.rounds_executed == 2  # totals 4.0 then 8.0
    ref = run_until(spec, inputs, init, mesh, max_rounds=8, min_chunk=1,
                    donate_state=False)
    np.testing.assert_array_equal(np.asarray(res.state), np.asarray(ref.state))
    np.testing.assert_array_equal(np.asarray(res.aux["total"]),
                                  np.asarray(ref.aux["total"]))


def test_run_until_preserves_callers_state_when_it_reshards():
    """Placing a one-device init_state onto a replicated 4-device layout
    can reuse the caller's buffer for device 0; run_until must still copy
    it before the first chunk donates, so the caller's array survives."""
    from conftest import run_in_subprocess

    out = run_in_subprocess("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.core.driver import IterativeSpec, run_until
        from repro.core.engine import identity_hash

        def reduce_fn(state, rk, rv, valid, r):
            got = jax.lax.psum(jnp.sum(jnp.where(valid, rv["v"], 0.0)), "data")
            return state + got, {"total": state + got}

        spec = IterativeSpec(
            map_fn=lambda state, inputs, r: (inputs["k"], {"v": inputs["v"]}),
            reduce_fn=reduce_fn, hash_fn=identity_hash, capacity=4)
        mesh = make_mesh((4,), ("data",))
        inputs = {"k": jnp.arange(8, dtype=jnp.int32) % 4,
                  "v": jnp.ones((8,), jnp.float32)}
        init = jnp.float32(1.0)
        res = run_until(spec, inputs, init, mesh, max_rounds=2)
        assert not init.is_deleted()
        print("init", float(init), "state", float(res.state))
    """, devices=4)
    assert "init 1.0 state 17.0" in out, out
