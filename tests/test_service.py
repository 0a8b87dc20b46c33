"""Persistent job service (`serve/service.py`): bucket ladder, runner-cache
counters + LRU eviction, env knob resolvers, warm-resubmit zero compiles,
interleaved == serial bit-identity at queue depth > 1, re-entrant wire
accounting across interleaved generators, admission-sim policy comparison."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core.driver import IterativeSpec, run_until_chunks
from repro.core.engine import identity_hash
from repro.core.shuffle import (
    SecureShuffleConfig,
    record_wire_bytes,
    wire_accounting,
)
from repro.crypto import chacha
from repro.runtime.sim import AdmissionSim, SimJob, burst_trace, straggler_trace
from repro.serve.service import (
    BUCKET_GROWTH_ENV,
    MAX_RUNNERS_ENV,
    RunnerCache,
    SecureJobService,
    bucket_for,
    resolve_bucket_growth,
    resolve_max_resident,
)


def _mesh1():
    return make_mesh((1,), ("data",))


def _secure_cfg():
    return SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x21" * 12),
        counter0=3,
    )


# one cache for every secure test in this module: the whole point of the
# service is that compiled programs amortize across jobs AND sessions
_SECURE_CACHE = RunnerCache()


# --- geometric bucket ladder --------------------------------------------------


def test_bucket_ladder_properties():
    """Rungs are >= n, aligned to `multiple`, and depend only on
    (multiple, growth) — every size in a rung's span shares the rung."""
    for n, want in [(1, 4), (4, 4), (5, 8), (9, 16), (17, 32), (100, 128)]:
        assert bucket_for(n, multiple=4, growth=2.0) == want
    # alignment + cover, across growth factors
    for growth in (1.5, 2.0, 4.0):
        for n in range(1, 200):
            b = bucket_for(n, multiple=8, growth=growth)
            assert b >= n and b % 8 == 0
    # the ladder is strictly increasing even when growth barely clears
    # the alignment unit (growth * multiple rounds back to multiple)
    assert bucket_for(9, multiple=8, growth=1.01) == 16
    # 1.1x a compiled size lands on the SAME rung (the reuse contract)
    assert bucket_for(110, growth=2.0) == bucket_for(100, growth=2.0) == 128
    with pytest.raises(ValueError, match="n >= 1"):
        bucket_for(0)
    with pytest.raises(ValueError, match="multiple >= 1"):
        bucket_for(4, multiple=0)


def test_bucket_growth_resolver_env(monkeypatch, no_calibration):
    monkeypatch.delenv(BUCKET_GROWTH_ENV, raising=False)
    assert resolve_bucket_growth() == 2.0
    assert resolve_bucket_growth(1.5) == 1.5
    monkeypatch.setenv(BUCKET_GROWTH_ENV, "1.25")
    assert resolve_bucket_growth("auto") == 1.25
    # an explicit value always wins over the environment
    assert resolve_bucket_growth(4.0) == 4.0
    # a bad ENV value must blame the env var by name
    monkeypatch.setenv(BUCKET_GROWTH_ENV, "spam")
    with pytest.raises(ValueError, match=r"\$REPRO_BUCKET_GROWTH"):
        resolve_bucket_growth("auto")
    monkeypatch.setenv(BUCKET_GROWTH_ENV, "1.0")
    with pytest.raises(ValueError, match=r"\$REPRO_BUCKET_GROWTH"):
        resolve_bucket_growth(None)
    # a bad EXPLICIT value must NOT blame the environment
    monkeypatch.delenv(BUCKET_GROWTH_ENV, raising=False)
    with pytest.raises(ValueError) as ei:
        resolve_bucket_growth(0.5)
    assert "$" not in str(ei.value)


def test_max_resident_resolver_env(monkeypatch, no_calibration):
    monkeypatch.delenv(MAX_RUNNERS_ENV, raising=False)
    assert resolve_max_resident("auto") is None
    assert resolve_max_resident(None) is None
    assert resolve_max_resident(3) == 3
    for unbounded in ("none", "0", "unbounded"):
        monkeypatch.setenv(MAX_RUNNERS_ENV, unbounded)
        assert resolve_max_resident("auto") is None
    monkeypatch.setenv(MAX_RUNNERS_ENV, "2")
    assert resolve_max_resident("auto") == 2
    monkeypatch.setenv(MAX_RUNNERS_ENV, "-3")
    with pytest.raises(ValueError, match=r"\$REPRO_SERVICE_MAX_RUNNERS"):
        resolve_max_resident("auto")
    monkeypatch.delenv(MAX_RUNNERS_ENV, raising=False)
    with pytest.raises(ValueError) as ei:
        resolve_max_resident(-1)
    assert "$" not in str(ei.value)


# --- runner cache -------------------------------------------------------------


def test_runner_cache_counters_and_lru_eviction():
    cache = RunnerCache(max_resident=2)

    def dead():  # a hit must never invoke the build closure
        raise AssertionError("build called on a cache hit")

    assert cache.get_or_build(("a",), lambda: "A") == "A"   # miss
    assert cache.get_or_build(("a",), dead) == "A"          # hit
    assert cache.get_or_build(("b",), lambda: "B") == "B"   # miss
    assert cache.get_or_build(("a",), dead) == "A"          # hit: a now MRU
    assert cache.get_or_build(("c",), lambda: "C") == "C"   # miss: evicts b
    assert cache.keys() == [("a",), ("c",)]
    s = cache.stats()
    assert (s["hits"], s["misses"], s["evictions"]) == (2, 3, 1)
    assert s["resident"] == 2 and s["max_resident"] == 2
    # the evicted entry is rebuilt on next request (a fresh miss)
    assert cache.get_or_build(("b",), lambda: "B2") == "B2"
    assert cache.stats()["misses"] == 4
    cache.clear()
    assert len(cache) == 0


def test_cache_view_keys_disjoint_across_secure_material():
    """Key/nonce/counter material is baked into traced closures, so it must
    key the cache: different material can never alias a runner."""
    cache = RunnerCache()
    mesh = _mesh1()

    def view(secure):
        return cache.view(spec_id=("w", 1), mesh=mesh, axis_name="data",
                          secure=secure)

    cfg = _secure_cfg()
    bases = [
        view(None).key_base,
        view(cfg).key_base,
        view(SecureShuffleConfig(key_words=chacha.key_to_words(b"\x07" * 32),
                                 nonce_words=cfg.nonce_words,
                                 counter0=cfg.counter0)).key_base,
        view(SecureShuffleConfig(key_words=cfg.key_words,
                                 nonce_words=cfg.nonce_words,
                                 counter0=cfg.counter0 + 1)).key_base,
    ]
    assert len(set(bases)) == len(bases)
    # identical material resolves to the identical base (shareable)
    assert view(_secure_cfg()).key_base == bases[1]
    # distinct workload identity splits the base too
    assert cache.view(spec_id=("w", 2), mesh=mesh,
                      axis_name="data").key_base != bases[0]


# --- service: warm resubmits -------------------------------------------------


def test_service_warm_resubmit_zero_compiles():
    """A same-bucket resubmit runs entirely on cached programs: zero runner
    misses AND zero new XLA compile-cache entries, with the second job's
    keystream budget reserved right after the first's."""
    rng = np.random.default_rng(0)
    pts = np.concatenate([rng.normal(-3, 0.1, (6, 2)),
                          rng.normal(3, 0.1, (6, 2))]).astype(np.float32)
    cache = RunnerCache()
    with SecureJobService(_mesh1(), cache=cache, max_concurrent=2) as svc:
        # min_chunk == max_chunk: every dispatch uses ONE chunk size, so
        # the warm claim cannot hinge on matching convergence trajectories
        h1 = svc.submit_kmeans(pts, 2, max_rounds=4, min_chunk=4, max_chunk=4)
        r1 = h1.result(timeout=300)
        assert h1.runner_misses > 0 and not h1.warm
        assert r1["halted"] and r1["n_iter"] >= 1
        assert h1.latency_s is not None and h1.queue_s is not None

        compiles_before = cache.compile_cache_size()
        h2 = svc.submit_kmeans(pts[:10], 2, max_rounds=4,
                               min_chunk=4, max_chunk=4)
        r2 = h2.result(timeout=300)
        assert h2.runner_misses == 0 and h2.warm
        assert cache.compile_cache_size() == compiles_before
        # n=10 and n=12 pad to the same geometric bucket
        assert h2.bucket == h1.bucket
        # disjoint keystream budgets: monotone round-base reservation
        assert h1.round_base == 0
        assert h2.round_base == h1.round_base + h1.max_rounds
        assert r2["halted"]
    assert svc.stats()["jobs_completed"] == 2


def test_submit_validation_and_closed_service():
    svc = SecureJobService(_mesh1())
    with pytest.raises(ValueError, match="k must be"):
        svc.submit_kmeans(np.zeros((4, 2), np.float32), 9)
    with pytest.raises(ValueError, match="values must be"):
        svc.submit_sort(np.zeros((0,), np.float32))
    with pytest.raises(ValueError, match="n_rounds must be"):
        svc.submit_grep(np.zeros((4,), np.int32), [1], n_rounds=0)
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit_grep(np.zeros((4,), np.int32), [1])


# --- two-level priority admission ---------------------------------------------


def test_priority_submit_admits_ahead_of_fifo():
    """With one slot busy, a later priority submit is admitted before the
    earlier normal one; the active job is never preempted."""
    import time as _time

    toks = (np.arange(16) % 5).astype(np.int32)
    with SecureJobService(_mesh1(), max_concurrent=1) as svc:
        with pytest.raises(ValueError, match="priority"):
            svc.submit_grep(toks, [1], priority=-1)
        ha = svc.submit_grep(toks, [1], n_rounds=2)          # fills the slot
        # wait until A OWNS the slot (admitted, compiling its runner) so B
        # and C verifiably queue behind it
        deadline = _time.perf_counter() + 120
        while ha.started_at is None:
            assert _time.perf_counter() < deadline, "job A never started"
            _time.sleep(0.001)
        hb = svc.submit_grep(toks, [2], n_rounds=2)          # queues (normal)
        hc = svc.submit_grep(toks, [3], n_rounds=2, priority=1)  # jumps queue
        for h in (ha, hb, hc):
            h.result(timeout=600)
    assert (ha.priority, hb.priority, hc.priority) == (0, 0, 1)
    # A kept its slot (admission order, not preemption)...
    assert ha.started_at < hc.started_at
    # ...and C was admitted ahead of the earlier-submitted B
    assert hc.started_at < hb.started_at
    assert hc.finished_at < hb.started_at  # one slot: strictly serial
    # keystream budgets still reserve in SUBMIT order (disjointness is
    # assigned at submit time, independent of admission order)
    assert hb.round_base == ha.round_base + ha.max_rounds
    assert hc.round_base == hb.round_base + hb.max_rounds


def test_admission_sim_priority_mirrors_service():
    """The sim's two-level admission: a priority job among the arrived
    prefix admits first; a priority job that has NOT arrived yet changes
    nothing; total work (makespan) is unchanged either way."""
    from dataclasses import replace as dc_replace

    sim = AdmissionSim(max_concurrent=1, min_chunk=8, max_chunk=8)
    jobs = [SimJob(0.0, 4096, 8), SimJob(0.0, 4096, 8),
            SimJob(0.0, 4096, 8, priority=1)]
    flat = [dc_replace(j, priority=0) for j in jobs]
    r, r_flat = sim.run(jobs, "bucketed"), sim.run(flat, "bucketed")
    lat, lat_flat = r["per_job_latency_s"], r_flat["per_job_latency_s"]
    # the priority job cut ahead of both normal jobs...
    assert lat[2] < lat[0] < lat[1]
    assert lat[2] < lat_flat[2]
    # ...without creating or destroying work
    assert r["makespan_s"] == pytest.approx(r_flat["makespan_s"])

    # a priority job arriving after the queue drains cannot jump anything:
    # identical replay to the all-normal trace
    late = [SimJob(0.0, 4096, 8), SimJob(0.0, 4096, 8),
            SimJob(1e6, 4096, 8, priority=1)]
    late_flat = [dc_replace(j, priority=0) for j in late]
    assert sim.run(late, "bucketed") == sim.run(late_flat, "bucketed")


# --- LRU eviction under interleaved live jobs ---------------------------------


def test_lru_eviction_of_live_jobs_runner_is_bitidentical():
    """Residency cap 1 + two interleaved jobs: every scheduler pass evicts
    the OTHER live job's runner, which is rebuilt (a fresh miss) on its next
    chunk. Results must be bit-identical to an unbounded cache — eviction
    costs recompiles, never correctness (round offsets, carried state, and
    keystream ranges live outside the evicted program)."""
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 7, (24,)).astype(np.int32)

    def run(cache):
        with SecureJobService(_mesh1(), secure=_secure_cfg(), cache=cache,
                              max_concurrent=2) as svc:
            # different pattern sets -> different cache keys; fixed chunk
            # size 1 -> each job needs its runner on every pass. Both are
            # queued before the scheduler can admit either (it waits on this
            # lock), so they are live together from the first pass
            with svc._cv:
                ha = svc.submit_grep(toks, [1, 2], n_rounds=2,
                                     min_chunk=1, max_chunk=1)
                hb = svc.submit_grep(toks, [3, 4, 5], n_rounds=2,
                                     min_chunk=1, max_chunk=1)
            return ha.result(timeout=600), hb.result(timeout=600)

    capped = RunnerCache(max_resident=1)
    ra_c, rb_c = run(capped)
    s = capped.stats()
    assert s["max_resident"] == 1 and s["resident"] <= 1
    # both jobs LIVE while their runners thrash: at least one eviction per
    # extra rebuild, and every post-eviction chunk re-misses
    assert s["evictions"] >= 2
    assert s["misses"] >= 4

    unbounded = RunnerCache()
    ra_u, rb_u = run(unbounded)
    assert unbounded.stats()["evictions"] == 0
    assert unbounded.stats()["misses"] == 2  # one compile per job, then hits
    for a, b in [(ra_c, ra_u), (rb_c, rb_u)]:
        assert a.keys() == b.keys()
        for key in a:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]), err_msg=key)


# --- service: interleaved vs serial (queue depth > 1) ------------------------


def _submit_three(svc, pts, vals, toks, pats):
    """The fixed submission order both runs share (same round bases)."""
    hk = svc.submit_kmeans(pts, 2, max_rounds=6, min_chunk=2, max_chunk=2)
    hs = svc.submit_sort(vals, max_rounds=3, min_chunk=1, max_chunk=2)
    hg = svc.submit_grep(toks, pats, n_rounds=2)
    return hk, hs, hg


def test_interleaved_bitidentical_to_serial_secure():
    """Three concurrent SECURE jobs whose chunk dispatches interleave on one
    mesh produce bit-identical results to the same submissions run one at a
    time — per-job round bases keep every keystream range disjoint, and each
    suspended generator owns its carried state."""
    rng = np.random.default_rng(7)
    pts = np.concatenate([rng.normal(-2, 0.2, (5, 2)),
                          rng.normal(2, 0.2, (5, 2))]).astype(np.float32)
    vals = rng.normal(0, 1, (9,)).astype(np.float32)
    toks = rng.integers(0, 5, (12,)).astype(np.int32)
    pats = np.array([1, 3], np.int32)

    def run(max_concurrent):
        with SecureJobService(_mesh1(), secure=_secure_cfg(),
                              cache=_SECURE_CACHE,
                              max_concurrent=max_concurrent) as svc:
            handles = _submit_three(svc, pts, vals, toks, pats)
            results = [h.result(timeout=600) for h in handles]
        return handles, results

    (hk, hs, hg), (rk, rs, rg) = run(max_concurrent=3)  # interleaved
    # depth 3 really interleaved: kmeans spans multiple scheduler passes
    assert hk.chunks > 1
    # the grep job was admitted at a NONZERO round base...
    assert hg.round_base == hk.max_rounds + hs.max_rounds
    # ...and still counts exactly like the host oracle (cursor-in-state:
    # the stream position is offset-agnostic; -1 bucket padding is inert)
    np.testing.assert_array_equal(
        rg["counts"], np.array([(toks == p).sum() for p in pats], np.float32))
    np.testing.assert_array_equal(np.sort(rs["sorted"]), np.sort(vals))

    (hk2, hs2, hg2), (rk2, rs2, rg2) = run(max_concurrent=1)  # serial
    for a, b in [(rk, rk2), (rs, rs2), (rg, rg2)]:
        assert a.keys() == b.keys()
        for key in a:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]), err_msg=key)
    # fresh service, shared cache: the serial rerun compiled NOTHING
    assert all(h.warm for h in (hk2, hs2, hg2))


# --- wire accounting: re-entrant across interleaved generators ----------------


def _tiny_spec(n=4):
    def map_fn(state, inputs, r):
        return jnp.zeros((n,), jnp.int32), {"v": jnp.ones((n,), jnp.float32)}

    def reduce_fn(state, rk, rv, valid, r):
        got = jax.lax.psum(jnp.sum(jnp.where(valid, rv["v"], 0.0)), "data")
        return state + got, {"got": got}

    return IterativeSpec(map_fn=map_fn, reduce_fn=reduce_fn,
                         hash_fn=identity_hash, capacity=n, n_rounds=1)


def _drain(gen):
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def test_wire_accounting_reentrant_interleaved_generators():
    """Two interleaved `run_until_chunks` jobs, each holding its own
    `record_wire_bytes` context open across suspensions: sinks are
    independent, records split by job tag, and contexts may exit OUT of
    stack order (the norm for generator-held contexts)."""
    mesh = _mesh1()
    inputs = {"x": jnp.zeros((4,), jnp.float32)}
    assert not wire_accounting.enabled

    ctx_a = record_wire_bytes()
    recs_a = ctx_a.__enter__()
    gen_a = run_until_chunks(_tiny_spec(), inputs, jnp.float32(0.0), mesh,
                             max_rounds=2, job_tag="job-A", runners={})
    next(gen_a)  # traces job A's runner: records land in the open sink(s)

    ctx_b = record_wire_bytes()
    recs_b = ctx_b.__enter__()
    gen_b = run_until_chunks(_tiny_spec(), inputs, jnp.float32(0.0), mesh,
                             max_rounds=2, job_tag="job-B", runners={})
    next(gen_b)  # traces job B's runner while BOTH sinks are open

    # out-of-LIFO exit: A entered first and leaves first, while B stays open
    ctx_a.__exit__(None, None, None)
    res_a = _drain(gen_a)
    res_b = _drain(gen_b)
    ctx_b.__exit__(None, None, None)

    # A's sink was open across BOTH traces — records split by job tag...
    assert {r["job"] for r in recs_a} == {"job-A", "job-B"}
    # ...while B's sink, opened after A's trace, holds only B's records
    assert {r["job"] for r in recs_b} == {"job-B"}
    # both jobs traced the SAME shuffle: byte-for-byte identical accounting
    by_job = lambda sink, tag: [r["bytes"] for r in sink if r["job"] == tag]
    assert by_job(recs_a, "job-A") == by_job(recs_a, "job-B") == by_job(
        recs_b, "job-B")
    assert all(r["bytes"] > 0 for r in recs_a)
    # interleaving didn't corrupt either job's actual result
    assert float(res_a.state) == float(res_b.state) == 2 * 4
    # the module-level stack is clean again
    assert not wire_accounting.enabled and not wire_accounting._sinks


def test_wire_accounting_shared_sink_splits_by_job_tag():
    """ONE outer sink spanning two interleaved jobs attributes every record
    to the job whose dispatch traced it."""
    mesh = _mesh1()
    inputs = {"x": jnp.zeros((4,), jnp.float32)}
    with record_wire_bytes() as recs:
        gen_a = run_until_chunks(_tiny_spec(), inputs, jnp.float32(0.0), mesh,
                                 max_rounds=1, job_tag=11, runners={})
        gen_b = run_until_chunks(_tiny_spec(), inputs, jnp.float32(0.0), mesh,
                                 max_rounds=1, job_tag=22, runners={})
        next(gen_a, None)
        next(gen_b, None)
        _drain(gen_a)
        _drain(gen_b)
    jobs = [r["job"] for r in recs]
    assert set(jobs) == {11, 22}
    assert jobs.index(11) < jobs.index(22)  # trace order preserved


# --- admission-policy testbed -------------------------------------------------


def test_admission_sim_bucketed_beats_compile_per_job():
    """On both canonical traces the bucketed policy wins virtual makespan
    (and compiles strictly less) than compile-per-job — the testbed claim
    the real service's bucket ladder rests on."""
    sim = AdmissionSim()
    for trace in (burst_trace(), straggler_trace()):
        bucketed = sim.run(trace, "bucketed")
        per_job = sim.run(trace, "compile-per-job")
        assert bucketed["makespan_s"] < per_job["makespan_s"]
        assert bucketed["compiles"] < per_job["compiles"]
        assert bucketed["mean_latency_s"] < per_job["mean_latency_s"]


def test_admission_sim_residency_cap_evicts():
    capped = AdmissionSim(max_resident=2)
    r = capped.run(burst_trace(), "bucketed")
    assert r["evictions"] > 0
    assert r["resident"] <= 2
    # the cap costs recompiles relative to the unbounded cache
    unbounded = AdmissionSim().run(burst_trace(), "bucketed")
    assert r["compiles"] >= unbounded["compiles"]
