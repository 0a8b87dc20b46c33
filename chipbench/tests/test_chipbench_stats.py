"""The end-to-end arithmetic on a known schedule of jobs."""

import statistics

import pytest

from chipbench import stats
from chipbench.catalog import BENCH_DIR, Catalog, _load_module

MiB = 1 << 20


def schedule():
    """Two clients, five jobs each: client 0's jobs take 1..5 s back to
    back from t=0, client 1's take 2 s each from t=0.5."""
    recs, t = [], 0.0
    for i in range(5):
        recs.append(stats.JobRecord(0, i % 2, t, t + i + 1, 64 * MiB))
        t += i + 1
    t = 0.5
    for i in range(5):
        recs.append(stats.JobRecord(1, i % 2, t, t + 2.0, 64 * MiB))
        t += 2.0
    return recs


def test_percentiles_on_known_schedule():
    lat = sorted(r.latency_s for r in schedule())
    assert lat == [1, 2, 2, 2, 2, 2, 2, 3, 4, 5]
    assert stats.percentile(lat, 50) == pytest.approx(2.0)
    # p90: rank 0.9 * 9 = 8.1 between the 9th (4 s) and 10th (5 s)
    assert stats.percentile(lat, 90) == pytest.approx(4.1)
    assert stats.percentile(lat, 50) == pytest.approx(statistics.median(lat))
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_drained_rate_spans_first_submit_to_last_completion():
    recs = schedule()
    # first submit at 0, last completion at 15 (client 0: 1+2+3+4+5)
    assert stats.drained_rate(recs, scale=MiB) == pytest.approx(10 * 64 / 15.0)


def test_metric_readers_on_known_schedule(tiny_root):
    readers = {m.name: m.reader for m in Catalog(tiny_root).metrics("end_to_end")}
    # the k-means cells' tail, kept for when they are back in BENCHMARK.json
    readers["job_p90_s"] = _load_module(BENCH_DIR / "metrics" / "job_p90_s.py")

    class Ctx:
        records = schedule()
        setup_s = 12.5

    assert readers["job_p50_s"].read(Ctx) == pytest.approx(2.0)
    assert readers["job_p90_s"].read(Ctx) == pytest.approx(4.1)
    assert readers["input_MiB_per_s"].read(Ctx) == pytest.approx(640 / 15.0)
    assert readers["setup_s"].read(Ctx) == 12.5


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0.0
    q1, q2, q3 = statistics.quantiles([9, 10, 10, 11, 12, 10], n=4)
    assert stats.spread([9, 10, 10, 11, 12, 10]) == pytest.approx((q3 - q1) / q2)
