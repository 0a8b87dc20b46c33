"""Spans and layer scopes of the secure job path (`repro/obs.py`).

One child process on four forced host devices runs two secure sorts
through `SecureJobService` under the profiler and reports what the trace
and the compiled program hold: the `repro.*` spans with their arguments,
each runner's `op_layers()`, and the same runner's program built again with
`jax.named_scope` made a no-op. The tests read that report.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import obs

CHILD = r"""
import contextlib, json, tempfile
from pathlib import Path

import numpy as np
import jax
from jax.profiler import ProfileData

from repro import obs
from repro.compat import make_mesh
from repro.core.shuffle import SecureShuffleConfig
from repro.crypto import chacha
from repro.serve.service import RunnerCache, SecureJobService

N = 2048
cfg = SecureShuffleConfig(key_words=chacha.key_to_words(bytes(range(32))),
                          nonce_words=chacha.nonce_to_words(b"\x21" * 12))
mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(7)
# NPB IS keys: bell-shaped, so the uniform splitters are refined once
data = [np.floor(rng.random((4, N)).mean(axis=0) * 2**19).astype(np.float32)
        for _ in range(2)]


def run_jobs(svc):
    handles = [svc.submit_sort(d) for d in data]
    results = [h.result(timeout=600) for h in handles]
    for d, res in zip(data, results):
        assert np.array_equal(res["sorted"], np.sort(d))
    return handles


svc = SecureJobService(mesh, secure=cfg, cache=RunnerCache())
run_jobs(svc)  # compiles outside the trace
tdir = tempfile.mkdtemp()
with jax.profiler.trace(tdir):
    handles = run_jobs(svc)
svc.close()

spans = []
pd = ProfileData.from_file(str(sorted(Path(tdir).rglob("*.xplane.pb"))[-1]))
for plane in pd.planes:
    for li, line in enumerate(plane.lines):
        for e in line.events:
            if e.name in obs.SPANS:
                spans.append({"name": e.name, "line": f"{plane.name}/{li}",
                              "start": e.start_ns, "end": e.end_ns,
                              "args": {k: int(v) for k, v in e.stats}})

runner = handles[0].runners[0]
text = runner.jitted.lower(*runner.arg_specs).compile().as_text()
layers = runner.op_layers()
searchsorted = [line.strip().removeprefix("ROOT ").partition(", metadata=")[0]
                for line in text.splitlines()
                if "searchsorted" in line and "/while/body/" in line
                and " fusion(" in line]

# the same runner, built and compiled with every layer scope a no-op
real_scope = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
try:
    svc2 = SecureJobService(mesh, secure=cfg, cache=RunnerCache())
    bare = svc2.submit_sort(data[0])
    bare.result(timeout=600)
    svc2.close()
finally:
    jax.named_scope = real_scope
bare_runner = bare.runners[0]
bare_text = bare_runner.jitted.lower(*bare_runner.arg_specs).compile().as_text()
bare_layers = bare_runner.op_layers()

print(json.dumps({
    "jobs": [{"id": h.job_id, "chunks": h.chunks, "runners": len(h.runners)}
             for h in handles],
    "spans": spans,
    "layers": sorted({v for v in layers.values() if v}),
    "searchsorted": {t: layers.get(t) for t in searchsorted},
    "scoped_equals_bare": obs.strip_metadata(text) == obs.strip_metadata(bare_text),
    "scoped_differs_with_metadata": text != bare_text,
    "bare_layers": sorted({v for v in bare_layers.values() if v}),
}))
"""


@pytest.fixture(scope="module")
def report():
    out = run_in_subprocess(CHILD, devices=4, timeout=900)
    return json.loads(out.strip().splitlines()[-1])


def _by_job(report, name):
    out: dict = {}
    for s in report["spans"]:
        if s["name"] == name:
            out.setdefault(s["args"].get("job"), []).append(s)
    return out


def test_every_job_has_its_spans(report):
    jobs = report["jobs"]
    assert len(jobs) == 2 and all(j["chunks"] >= 1 for j in jobs)
    assert any(j["chunks"] == 2 for j in jobs)  # refined: two dispatches
    for name in obs.SPANS:
        assert None not in _by_job(report, name), f"{name} without a job id"
    submit, prepare, finalize = (_by_job(report, n) for n in
                                 (obs.SUBMIT, obs.PREPARE, obs.FINALIZE))
    dispatch, readback = _by_job(report, obs.DISPATCH), _by_job(report, obs.READBACK)
    for j in jobs:
        jid = j["id"]
        assert len(submit[jid]) == 1 and len(finalize[jid]) == 1
        assert len(prepare[jid]) >= 1
        for spans in (dispatch[jid], readback[jid]):
            assert sorted(s["args"]["chunk"] for s in spans) == list(range(j["chunks"]))


def test_scheduler_spans_nest_in_no_other_jobs_span(report):
    spans = report["spans"]
    for s in spans:
        if s["name"] not in (obs.DISPATCH, obs.READBACK):
            continue
        for o in spans:
            if o["line"] == s["line"] and o["args"]["job"] != s["args"]["job"]:
                assert not (o["start"] <= s["start"] and s["end"] <= o["end"]), (s, o)
    # a job's spans on the scheduler thread run in order
    for jid in {s["args"]["job"] for s in spans}:
        mine = sorted((s for s in spans if s["args"]["job"] == jid
                       and s["name"] != obs.SUBMIT), key=lambda s: s["start"])
        assert mine[0]["name"] == obs.PREPARE and mine[-1]["name"] == obs.FINALIZE


def test_op_layers_name_the_shuffle_layers(report):
    assert {obs.BUCKET_PACK, obs.KEYSTREAM, obs.EXCHANGE, obs.REDUCE,
            obs.MAP} <= set(report["layers"])
    assert report["jobs"][0]["runners"] >= 1


def test_searchsorted_loop_fusions_map_to_a_layer(report):
    loop = report["searchsorted"]
    assert loop, "no fusion of a searchsorted loop body in the compiled text"
    assert None not in loop.values(), loop
    # bucket_pack's search over its sorted buckets; the map's over the
    # splitters; the reduce's in the splitters' refinement
    assert set(loop.values()) == {obs.BUCKET_PACK, obs.MAP, obs.REDUCE}, loop


def test_scopes_leave_the_compiled_program_unchanged(report):
    assert report["scoped_equals_bare"]
    assert report["scoped_differs_with_metadata"]
    assert report["bare_layers"] == []


def test_layer_of_reads_the_innermost_scope():
    assert obs.layer_of("jit(run)/while/body/reduce/sort") == "reduce"
    assert obs.layer_of("jit(run)/map/bucket_pack/jit(searchsorted)/while/body/gather") \
        == "bucket_pack"
    # the last component is the primitive: `lax.reduce` is not a scope
    assert obs.layer_of("jit(run)/bucket_pack/reduce") == "bucket_pack"
    assert obs.layer_of("jit(run)/while/body/add") is None
    assert obs.layer_of("") is None


def test_op_layers_and_strip_metadata_on_text():
    text = "\n".join([
        "HloModule jit_run, entry_computation_layout={(f32[8]{0})->f32[8]{0}}",
        "",
        "FileNames",
        '1 "driver.py"',
        "",
        "ENTRY %main.3 (p.1: f32[8]) -> f32[8] {",
        "  %p.1 = f32[8]{0} parameter(0)",
        '  %sort.2 = f32[8]{0} sort(%p.1), dimensions={0}, '
        'metadata={op_name="jit(run)/reduce/sort" stack_frame_id=1}',
        '  ROOT %add.3 = f32[8]{0} add(%sort.2, %sort.2), metadata={op_name="jit(run)/add"}',
        "}",
    ])
    assert obs.op_layers(text) == {
        "%p.1 = f32[8]{0} parameter(0)": None,
        "%sort.2 = f32[8]{0} sort(%p.1), dimensions={0}": "reduce",
        "%add.3 = f32[8]{0} add(%sort.2, %sort.2)": None,
    }
    stripped = obs.strip_metadata(text)
    assert "metadata" not in stripped and "driver.py" not in stripped
    assert "%sort.2 = f32[8]{0} sort(%p.1), dimensions={0}" in stripped


def test_submitted_at_is_stamped_on_entry(monkeypatch):
    from repro.compat import make_mesh
    from repro.serve import service as service_mod

    stamps = {}
    real = service_mod.bucket_for

    def slow_bucket_for(*a, **k):
        stamps["bucket"] = time.perf_counter()
        time.sleep(0.05)
        return real(*a, **k)

    monkeypatch.setattr(service_mod, "bucket_for", slow_bucket_for)
    with service_mod.SecureJobService(make_mesh((1,), ("data",))) as svc:
        t0 = time.perf_counter()
        h = svc.submit_sort(np.arange(64, dtype=np.float32)[::-1].copy())
        t1 = time.perf_counter()
        assert np.array_equal(h.result(timeout=300)["sorted"], np.arange(64, dtype=np.float32))
    assert t0 <= h.submitted_at < stamps["bucket"] < t1
    assert h.queue_s >= 0.05 and h.latency_s >= h.queue_s
