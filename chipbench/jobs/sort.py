"""A sort of NPB IS keys through the service: data, submit, reference.

Keys follow the NAS Parallel Benchmarks' IS kernel: each is the floor of
max_key times the mean of four uniform draws, so they are integers in
[0, max_key), bell-shaped and full of duplicates. NPB draws them from its
own linear congruential generator; here numpy's generator draws them from
the seed. The service sorts float32 values, which hold these integers
exactly (max_key <= 2^24).

Reference: numpy's sort of the same keys. The comparison is exact.
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("bfloat16",)  # `control` variants


def make_data(config: dict, n: int, seed: int, slot: int) -> dict:
    rng = np.random.default_rng([seed, slot])
    max_key = config["max_key"]
    if max_key > 1 << 24:
        raise ValueError(f"max_key {max_key} is not exact in float32")
    u = rng.random((4, n), dtype=np.float32).sum(axis=0, dtype=np.float64) / 4.0
    keys = np.floor(u * max_key).astype(np.float32)
    return {"keys": keys, "input_bytes": keys.nbytes}


def submit(service, data: dict, config: dict):
    return service.submit_sort(data["keys"], balance=config["balance"],
                               max_rounds=config["max_rounds"])


def rounds(result: dict) -> int:
    return int(result["rounds"])


def bucket(n: int, n_shards: int) -> int:
    """The service's padded size for n keys: a power-of-two multiple of R
    (`bucket_for` at its default growth of 2)."""
    b = n_shards
    while b < n:
        b *= 2
    return b


def wire_payload_bytes(config: dict, n: int, n_shards: int) -> int:
    """Bytes of one shard's coalesced shuffle wire in one round.

    Every destination row holds the default lossless capacity of
    bucket / R slots, each a key (int32) and a value (f32).
    """
    cap = bucket(n, n_shards) // n_shards
    return n_shards * cap * 2 * 4


def reference(config: dict, data: dict, results: list) -> dict:
    return {"sorted": np.sort(data["keys"])}


def compare(config: dict, ref: dict, result: dict) -> dict:
    """Positions at which the output differs from numpy's sort, counting
    every key missing or extra."""
    got = np.asarray(result["sorted"])
    want = ref["sorted"]
    m = min(got.shape[0], want.shape[0])
    wrong = int(np.count_nonzero(got[:m] != want[:m])) + abs(got.shape[0] - want.shape[0])
    return {"mismatched": wrong}


def control(config: dict, data: dict, ref: dict, *, accumulate: str = "bfloat16") -> dict:
    """The reference computed in bfloat16: the keys rounded to bfloat16
    and sorted."""
    import ml_dtypes

    keys = data["keys"].astype(ml_dtypes.bfloat16).astype(np.float32)
    return {"sorted": np.sort(keys), "rounds": 1}
