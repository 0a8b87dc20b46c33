"""Faults planted under the timed path, for the tests that see `correct`
come out false: the service's workload specs are wrapped so that the
compiled round misbehaves, and everything above them runs as in a run."""

from __future__ import annotations

from dataclasses import replace

import jax.numpy as jnp


def stale_state(spec):
    """Every round returns the carried state it was given."""
    orig = spec.reduce_fn

    def reduce_fn(state, rk, rv, valid, r):
        _, aux = orig(state, rk, rv, valid, r)
        return state, aux

    return replace(spec, reduce_fn=reduce_fn)


def half_batch(spec):
    """The second half of every shard's input is left out of the map."""
    orig = spec.map_fn

    def map_fn(state, inputs, r):
        if "w" in inputs:  # k-means: weight 0, so the mean is over the rest
            w = inputs["w"]
            inputs = dict(inputs, w=w.at[w.shape[0] // 2:].set(0.0))
        else:  # sort: non-finite values are invalid records
            v = inputs["v"]
            inputs = dict(inputs, v=v.at[v.shape[0] // 2:].set(jnp.inf))
        return orig(state, inputs, r)

    return replace(spec, map_fn=map_fn)


def altered_answer(spec):
    """One value of the answer is changed where the reducer produces it."""
    orig = spec.reduce_fn

    def reduce_fn(state, rk, rv, valid, r):
        new, aux = orig(state, rk, rv, valid, r)
        if "sorted" in new:  # sort: the reducer's first sorted key
            new = dict(new, sorted=new["sorted"].at[0, 0].add(1.0))
        else:  # k-means: one coordinate of one center, by a tenth of the box
            new = dict(new, c=new["c"].at[0, 0].add(0.1))
        return new, aux

    return replace(spec, reduce_fn=reduce_fn)


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "altered_answer": altered_answer}


def plant(monkeypatch, fault: str):
    """Wrap the specs the service builds for every job kind."""
    import repro.serve.service as service

    for name in ("make_kmeans_iterative_spec", "make_sample_sort_spec"):
        orig = getattr(service, name)
        monkeypatch.setattr(service, name,
                            lambda *a, _o=orig, **k: FAULTS[fault](_o(*a, **k)))


def drop_exchange(monkeypatch):
    """The shuffle returns each shard's send buffer: nothing crosses chips."""
    import repro.core.driver as driver

    monkeypatch.setattr(driver, "keyed_all_to_all", lambda tree, *a, **k: tree)
