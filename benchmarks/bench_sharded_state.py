"""Sharded vs replicated carried state: per-device bytes + collective counts.

The driver's two-tier carried-state contract (`core/driver.py`) lets a large
per-reducer leaf — the sampling sort's (R, R·capacity) sorted table — stay
`P(axis)`-resident across rounds instead of being re-replicated by an
all_gather every round. This benchmark measures exactly what that buys on
the paper's sort workload, two independent ways:

  * structural counts — collective primitives per fused sort round, sharded
    vs replicated, by jaxpr inspection (`repro.tools.jaxprs
    .collective_counts`). Sharded must trace exactly ONE all_to_all (the
    shuffle) and exactly one FEWER all_gather (the table gather is gone)
    with zero other collectives added or removed (asserted, secure and
    plaintext);
  * per-device state bytes — the carried state actually resident on one
    device of a multi-device mesh (`benchmarks/multidevice.py`: every
    attached chip in this process, or 8 forced host devices in a CPU child),
    measured off the final state's `addressable_shards`. The sharded table
    keeps one (1, R·capacity) row per device vs the full (R, R·capacity)
    replica — the dominant leaf shrinks ~Rx, and the total must shrink by at
    least half the device count (asserted). The gathered outputs must be
    bit-identical across layouts (asserted).

Machine-readable output: `run()` fills the module-level `LAST_METRICS`
dict, which `benchmarks/run.py` serializes to BENCH_sharded_state.json
(uploaded by the CI bench-smoke lane alongside the other BENCH artifacts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.multidevice import run_section
from repro.compat import make_mesh
from repro.core.driver import make_iterative_runner
from repro.core.shuffle import SecureShuffleConfig
from repro.core.sort import make_sample_sort_spec
from repro.crypto import chacha
from repro.tools.jaxprs import collective_counts

# Filled by run(); serialized by benchmarks/run.py into BENCH_sharded_state.json.
LAST_METRICS: dict = {}

_STATE_SECTION = """
import numpy as np
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.driver import run_until
from repro.core.sort import make_sample_sort_spec

n = n_dev * capacity
mesh = make_mesh((n_dev,), ("data",))
rng = np.random.default_rng(0)
v = jnp.asarray((rng.exponential(scale=0.15, size=n) % 1.0).astype(np.float32))
edges = jnp.asarray(np.linspace(0.0, 1.001, n_dev + 1), jnp.float32)
out = {}
for sharded in (False, True):
    spec = make_sample_sort_spec(n_dev, capacity, halt_total=n,
                                 shard_state=sharded)
    init = {"edges": edges,
            "sorted": jnp.full((n_dev, n_dev * capacity), jnp.inf, jnp.float32),
            "counts": jnp.zeros((n_dev,), jnp.float32)}
    res = run_until(spec, {"v": v}, init, mesh, max_rounds=n_rounds,
                    warn_on_overflow=False)
    # bytes of carried state RESIDENT on device 0: a replicated leaf
    # contributes its full size, a P(axis) leaf only its local shard
    per_leaf = {k: l.addressable_shards[0].data.nbytes
                for k, l in res.state.items()}
    out[str(sharded)] = {
        "per_device_state_bytes": sum(per_leaf.values()),
        "per_leaf_device_bytes": per_leaf,
        "global_state_bytes": sum(l.nbytes for l in jax.tree.leaves(res.state)),
        "rounds_executed": res.rounds_executed,
        "halted": bool(res.halted),
        "sorted": np.asarray(res.state["sorted"]).tolist(),
        "counts": np.asarray(res.state["counts"]).tolist(),
    }
"""


def _cfg() -> SecureShuffleConfig:
    return SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x09" * 12),
        impl="pallas-interpret",
    )


def _sort_round_counts(shard_state: bool, secure) -> dict:
    """Collective counts of one traced fused sort chunk (1-axis mesh)."""
    mesh = make_mesh((1,), ("data",))
    r, n = 1, 64
    spec = make_sample_sort_spec(r, n, halt_total=n, shard_state=shard_state)
    runner = make_iterative_runner(spec, mesh, secure=secure)
    inputs = {"v": jnp.zeros((n,), jnp.float32)}
    state = {
        "edges": jnp.zeros((r + 1,), jnp.float32),
        "sorted": jnp.full((r, r * n), jnp.inf, jnp.float32),
        "counts": jnp.zeros((r,), jnp.float32),
    }
    jaxpr = jax.make_jaxpr(runner.abstract_fn)(inputs, state, jnp.uint32(0))
    return collective_counts(jaxpr)


def run(smoke: bool = False):
    global LAST_METRICS
    rows = []
    metrics: dict = {"smoke": smoke, "sort_round_collectives": {},
                     "per_device_state": {}}

    # --- structural counts: the fused sort round, sharded vs replicated ------
    for secure, sec_label in ((None, "plaintext"), (_cfg(), "secure")):
        sharded = _sort_round_counts(True, secure)
        replicated = _sort_round_counts(False, secure)
        metrics["sort_round_collectives"][sec_label] = {
            "sharded": sharded, "replicated": replicated}
        assert sharded["all_to_all"] == replicated["all_to_all"] == 1, (
            sec_label, sharded, replicated)
        assert replicated["all_gather"] == sharded["all_gather"] + 1, (
            sec_label, sharded, replicated)
        assert all(sharded[k] == replicated[k]
                   for k in sharded if k != "all_gather"), (sharded, replicated)
        rows.append((f"sort_round_collectives_{sec_label}", 0.0,
                     f"all_to_all={sharded['all_to_all']};"
                     f"all_gather={sharded['all_gather']}(sharded)"
                     f"vs{replicated['all_gather']}(replicated)"))

    # --- per-device carried-state bytes on a multi-device mesh ---------------
    n_dev = 8
    capacity = 64 if smoke else 256
    state = run_section(_STATE_SECTION, dict(capacity=capacity, n_rounds=3),
                        n_dev=n_dev, timeout=1800)
    n_dev = state["devices"]["count"]
    rep, sh = state["False"], state["True"]
    # identical results is the precondition that makes the bytes comparable
    assert sh["sorted"] == rep["sorted"] and sh["counts"] == rep["counts"], (
        "sharded and replicated sort state diverged")
    assert sh["rounds_executed"] == rep["rounds_executed"]
    ratio = rep["per_device_state_bytes"] / max(sh["per_device_state_bytes"], 1)
    for side in (rep, sh):  # the gathered values are not trajectory metrics
        side.pop("sorted"), side.pop("counts")
    metrics["per_device_state"] = {
        "n_dev": n_dev, "capacity": capacity, "devices": state["devices"],
        "replicated": rep, "sharded": sh, "ratio": ratio,
    }
    # the (R, R*capacity) table dominates: per-device state must shrink by at
    # least half the device count (the table itself shrinks ~R-fold;
    # edges/counts stay tiny) — >=4x on the CPU's 8 forced devices
    assert ratio >= n_dev / 2, (
        f"sharded state must be >={n_dev / 2}x smaller per device on {n_dev} devices, "
        f"got {ratio:.2f}x ({rep['per_device_state_bytes']} -> "
        f"{sh['per_device_state_bytes']} bytes)")
    rows.append(("sort_state_bytes_per_device_replicated", 0.0,
                 f"bytes={rep['per_device_state_bytes']}"))
    rows.append(("sort_state_bytes_per_device_sharded", 0.0,
                 f"bytes={sh['per_device_state_bytes']};ratio={ratio:.2f}x"))

    LAST_METRICS = metrics
    return rows
