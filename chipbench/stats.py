"""Arithmetic on the window's job records: percentiles, rates, spreads.

A record is one job: when its client called `submit_*` and when `.result()`
returned, both on the client's `time.perf_counter()`, and the bytes of its
input. Pure functions, so the tests can check them on a known schedule.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass


@dataclass(frozen=True)
class JobRecord:
    client: int
    dataset: int
    submit_s: float
    done_s: float
    input_bytes: int

    @property
    def latency_s(self) -> float:
        return self.done_s - self.submit_s


def percentile(values, q: float) -> float:
    """The q-th percentile (0 < q < 100), linear between order statistics.

    `statistics.quantiles(method="inclusive")` on 100 cut points: the same
    number numpy's default gives, and defined for any sample of two or more.
    """
    values = list(values)
    if not values:
        raise ValueError("percentile of an empty sample")
    if len(values) == 1:
        return float(values[0])
    if not 0 < q < 100:
        raise ValueError(f"q must be in (0, 100), got {q}")
    cuts = statistics.quantiles(values, n=1000, method="inclusive")
    return float(cuts[round(q * 10) - 1])


def drained_rate(records, scale: float = 1.0) -> float:
    """Input bytes of every completed job over the time from the first
    submit to the last completion, the drain after the window included."""
    if not records:
        raise ValueError("no completed jobs")
    t0 = min(r.submit_s for r in records)
    t1 = max(r.done_s for r in records)
    return sum(r.input_bytes for r in records) / (t1 - t0) / scale


def spread(values) -> float:
    """Distance between the first and third quartile over the median, as
    `statistics.quantiles(values, n=4)` gives them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
