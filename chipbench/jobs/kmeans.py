"""The paper's k-means job (arXiv:1705.05684 §V): data, submit, reference.

Data: n points in [0, 1]^d around k Gaussian centers (the paper's
generator, copied from `repro.core.kmeans.generate_points` so that the
program cannot move it). The centers and the first k points, which are the
initial centers, come from the configuration's fixed layouts: each dataset
slot of a run has its own layout, so a slot converges in the same number of
rounds under every seed, and the seed draws the other n - k points.

Reference: Lloyd's rounds in float64 numpy from the same initial centers,
halting by the paper's rule (mean center shift under diag/1000).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 20
CONTROLS = ("bfloat16", "float32")  # `control` variants


def layout(config: dict, slot: int):
    """(true centers (k, d), initial centers (k, d)) of one dataset slot."""
    k, d, spread = config["k"], config["d"], config["spread"]
    lo, hi = config["center_range"]
    rng = np.random.default_rng([config["layout_seed"], slot])
    centers = rng.uniform(lo, hi, size=(k, d))
    first = centers[rng.integers(0, k, size=k)] + rng.normal(scale=spread, size=(k, d))
    return centers, first.astype(np.float32)


def make_data(config: dict, n: int, seed: int, slot: int) -> dict:
    """Points of one dataset slot; the first k are its initial centers."""
    k, d, spread = config["k"], config["d"], config["spread"]
    centers, first = layout(config, slot)
    rng = np.random.default_rng([seed, slot])
    idx = rng.integers(0, k, size=n - k)
    pts = np.empty((n, d), np.float32)
    pts[:k] = first
    pts[k:] = centers[idx] + rng.normal(scale=spread, size=(n - k, d))
    return {"points": pts, "input_bytes": pts.nbytes}


def submit(service, data: dict, config: dict):
    return service.submit_kmeans(data["points"], config["k"],
                                 max_rounds=config["max_rounds"])


def rounds(result: dict) -> int:
    return int(result["n_iter"])


def wire_payload_bytes(config: dict, n: int, n_shards: int) -> int:
    """Bytes of one shard's coalesced shuffle wire in one round.

    The map emits one partial per center: key (int32), count (f32) and sum
    (d x f32). Each destination row holds ceil(k / R) of them.
    """
    cap = -(-config["k"] // n_shards)
    return n_shards * cap * (2 + config["d"]) * 4


def _round(x: np.ndarray, c: np.ndarray, pool: ThreadPoolExecutor):
    """One Lloyd round in float64: per-center sums and counts."""
    k, d = c.shape
    c2 = np.sum(c * c, axis=1)

    def part(s):
        xs = x[s:s + CHUNK].astype(np.float64)
        a = np.argmin(c2 - 2.0 * (xs @ c.T), axis=1)
        sums = np.stack([np.bincount(a, weights=xs[:, j], minlength=k) for j in range(d)], 1)
        return sums, np.bincount(a, minlength=k).astype(np.float64)

    parts = list(pool.map(part, range(0, x.shape[0], CHUNK)))
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def lloyd(points: np.ndarray, init: np.ndarray, *, threshold: float, max_rounds: int,
          min_rounds: int = 0) -> dict:
    """Centers after each round, and the round at which the rule halts.

    Empty clusters keep their center, as the service's reduce does. Runs on
    to `min_rounds` past the halt, so that a program that ran longer can be
    compared round for round.
    """
    c = np.asarray(init, np.float64)
    history, counts_history, halt = [], [], None
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for r in range(1, max_rounds + 1):
            sums, counts = _round(points, c, pool)
            new = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], c)
            shift = float(np.mean(np.linalg.norm(new - c, axis=1)))
            c = new
            history.append(c.copy())
            counts_history.append(counts)
            if halt is None and shift < threshold:
                halt = r
            if halt is not None and r >= min_rounds:
                break
    return {"centers": history, "counts": counts_history, "halt": halt or max_rounds}


def diagonal(points: np.ndarray) -> float:
    return float(np.linalg.norm(points.max(axis=0).astype(np.float64)
                                - points.min(axis=0).astype(np.float64)))


def reference(config: dict, data: dict, results: list) -> dict:
    pts = data["points"]
    diag = diagonal(pts)
    # the service's own threshold is computed in float32 from the points
    thr = float(np.linalg.norm(pts.max(axis=0) - pts.min(axis=0))) / 1000.0
    need = max([rounds(r) for r in results], default=0)
    ref = lloyd(pts, pts[:config["k"]], threshold=thr,
                max_rounds=config["max_rounds"], min_rounds=need)
    ref["diag"] = diag
    return ref


def compare(config: dict, ref: dict, result: dict) -> dict:
    """The numbers checked for one job, each against its limit.

    `center_err_pts`: the largest gap between a center of the program and
    of the reference after the program's rounds, in units of what one point
    a diagonal away pulls that center: |gap| x (points in its cluster) /
    diag. A point that rounding puts in the neighbouring cluster moves a
    center by less than one such unit, at any n; the number does not grow
    with the dataset. `round_gap`: rounds run minus the reference's.
    """
    n_iter = rounds(result)
    r = min(max(n_iter, 1), len(ref["centers"])) - 1
    got = np.asarray(result["centers"], np.float64)
    gap = np.max(np.abs(got - ref["centers"][r]), axis=1)
    err = float(np.max(gap * np.maximum(ref["counts"][r], 1))) / ref["diag"]
    if not np.all(np.isfinite(got)):
        err = float("inf")
    return {"center_err_pts": err, "round_gap": abs(n_iter - ref["halt"])}


def control(config: dict, data: dict, ref: dict, *, accumulate: str) -> dict:
    """The reference computed in bfloat16 on the default device, put in the
    program's place: same initial centers, same number of rounds.

    `accumulate="bfloat16"` keeps every operation in bfloat16 (the
    precision below the configuration's float32); "float32" rounds the
    points and centers to bfloat16 but sums them in float32, as a
    bfloat16 matrix unit would.
    """
    import jax
    import jax.numpy as jnp

    acc = jnp.dtype(accumulate)
    k = config["k"]

    @jax.jit
    def step(x, c):
        cb = c.astype(jnp.bfloat16)
        d2 = jnp.sum(cb * cb, axis=1) - 2.0 * (x @ cb.T)
        a = jnp.argmin(d2, axis=1)
        sums = jax.ops.segment_sum(x.astype(acc), a, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones(a.shape, acc), a, num_segments=k)
        new = sums / jnp.maximum(counts, 1)[:, None]
        return jnp.where(counts[:, None] > 0, new, c.astype(acc)).astype(jnp.float32)

    x = jnp.asarray(data["points"]).astype(jnp.bfloat16)
    c = jnp.asarray(data["points"][:k])
    for _ in range(ref["halt"]):
        c = step(x, c)
    return {"centers": np.asarray(c), "n_iter": ref["halt"]}
