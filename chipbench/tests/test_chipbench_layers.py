"""The readers of the program's own layers and spans, on traces whose
answers are known, and one traced run of a tiny four-chip sort cell on four
host CPU devices split by `attribute.py`."""

import json
from types import SimpleNamespace

import pytest

from chipbench import attribute, layers, trace
from chipbench.catalog import BENCH_DIR, _load_module
from chipbench.tests.conftest import run_python

E = trace.Event
MS = 1_000_000  # ns

READERS = {name: _load_module(BENCH_DIR / "metrics" / f"{name}.py")
           for name in ("bucket_pack.ms_per_job", "reduce.ms_per_job",
                        "service.prepare_ms_per_job", "service.finalize_ms_per_job")}

# as the compiled text prints them (no operand shapes) ...
PACK = "%fusion.29 = s32[8]{0:T(1024)} fusion(%p.1, %p.2), kind=kLoop, calls=%fc.29"
SORT = "%sort.4 = (f32[8]{0}, s32[8]{0}) sort(%a, %b), dimensions={0}, to_apply=%cmp"
COPY = "%copy.3 = f32[8]{0} copy(%x)"
# ... and as the trace does
PACK_T = ("%fusion.29 = s32[8]{0:T(1024)S(1)} fusion(s32[8]{0} %p.1, s32[8]{0} %p.2), "
          "kind=kLoop, calls=%fc.29")
SORT_T = ("%sort.4 = (f32[8]{0:T(128)}, s32[8]{0:T(128)S(1)}) sort(f32[8]{0} %a, "
          "s32[8]{0} %b), dimensions={0}, to_apply=%cmp")
COPY_T = "%copy.3 = f32[8]{0:T(128)} copy(f32[8]{0} %x)"


class _Runner:
    def __init__(self, layer_map):
        self.map, self.calls = layer_map, 0

    def op_layers(self):
        self.calls += 1
        return self.map


def _trace(devices=2):
    """A window of 1000 ms; on each device 300 ms of bucket_pack (two
    fusions and a `while` that holds one), 40 ms of reduce, 10 ms of a
    copy in no layer and, after the window, more of both."""
    tr = trace.Trace()
    for d in range(devices):
        evs = [E("%while.5 = (s32[]) while(%t)", 0, 400 * MS), E(PACK_T, 0, 100 * MS),
               E(PACK_T, 100 * MS, 300 * MS), E(SORT_T, 300 * MS, 340 * MS),
               E(COPY_T, 500 * MS, 510 * MS), E(PACK_T, 2000 * MS, 2500 * MS)]
        tr.ops[d] = trace._leaves(evs)
        tr.busy[d] = trace.merge((e.start, e.end) for e in evs)
    tr.spans = [E("bench.submit", 0, 5 * MS), E("bench.wait", 5 * MS, 1000 * MS)]
    tr.host = [E("repro.prepare", 340 * MS, 360 * MS), E("repro.prepare", 360 * MS, 370 * MS),
               E("repro.finalize", 420 * MS, 480 * MS), E("repro.dispatch", 0, 1 * MS),
               E("np.asarray(jax.Array)", 420 * MS, 480 * MS),
               E("repro.finalize", 3000 * MS, 3100 * MS)]
    return tr


def _ctx(tr, handles):
    return SimpleNamespace(trace=tr, handles=handles)


def test_op_key_matches_the_trace_to_the_compiled_text():
    assert layers.op_key(PACK) == layers.op_key(PACK_T) == "%fusion.29 = s32[8] fusion"
    assert layers.op_key(SORT) == layers.op_key(SORT_T) == "%sort.4 = (f32[8],s32[8]) sort"
    assert layers.op_key(COPY) == layers.op_key(COPY_T)
    assert layers.op_key(PACK) != layers.op_key(PACK.replace("s32[8]", "s32[16]", 1))


def test_layer_map_joins_runners_and_marks_disagreement():
    a = _Runner({PACK: "bucket_pack", SORT: "reduce", COPY: None})
    b = _Runner({PACK: "bucket_pack", COPY: "map"})
    handles = [SimpleNamespace(runners=[a]), SimpleNamespace(runners=[a, b])]
    got = layers.layer_map(handles)
    assert got == {layers.op_key(PACK): "bucket_pack", layers.op_key(SORT): "reduce",
                   layers.op_key(COPY): layers.AMBIGUOUS}
    assert a.calls == 1  # each runner once


def test_readers_on_a_known_trace():
    runner = _Runner({PACK: "bucket_pack", SORT: "reduce", COPY: None})
    handles = [SimpleNamespace(runners=[runner]), SimpleNamespace(runners=[runner])]
    ctx = _ctx(_trace(devices=2), handles)
    got = {name: r.read(ctx) for name, r in READERS.items()}
    assert got == pytest.approx({
        "bucket_pack.ms_per_job": 300 / 2,  # per device, per job
        "reduce.ms_per_job": 40 / 2,
        "service.prepare_ms_per_job": 30 / 2,  # both spans, in the window only
        "service.finalize_ms_per_job": 60 / 2,
    })


@pytest.mark.parametrize("program", ["no runners", "no spans", "no trace"])
def test_readers_read_nothing_from_a_program_without_them(program):
    tr = _trace()
    handles = [SimpleNamespace(runners=[_Runner({PACK: "bucket_pack", SORT: "reduce"})])]
    if program == "no runners":  # a job handle from before `JobHandle.runners`
        handles = [SimpleNamespace(chunks=1)]
        tr.host = [e for e in tr.host if not e.name.startswith("repro.")]
    elif program == "no spans":
        tr.host = [e for e in tr.host if not e.name.startswith("repro.")]
    else:
        tr = None
    got = {name: r.read(_ctx(tr, handles)) for name, r in READERS.items()}
    if program == "no spans":
        assert got["bucket_pack.ms_per_job"] == pytest.approx(300.0)
        got = {k: v for k, v in got.items() if k.startswith("service.")}
    assert set(got.values()) == {None}


def test_attribute_splits_idle_time_by_span():
    tr = _trace(devices=1)
    idle, left = attribute.idle_split(tr)
    # idle: 400-500 and 510-1000 ms; prepare covers 340-370 (busy), finalize 420-480
    assert idle["idle"] == pytest.approx(0.590)
    assert idle["repro.finalize"] == pytest.approx(0.060)
    assert idle["repro.prepare"] == 0 and idle["repro.dispatch"] == 0
    assert idle["none"] == pytest.approx(0.530)
    assert left[0] == ["bench.wait | no host event", pytest.approx(0.490)]

    layer_of = {layers.op_key(PACK): "bucket_pack", layers.op_key(SORT): "reduce"}
    split = attribute.layer_split(tr, layer_of, {0: [(0, 450 * MS)]})
    assert split == pytest.approx({"bucket_pack": 0.3, "reduce": 0.04})  # the copy is outside


FOUR = """
import json, tempfile
from pathlib import Path
from chipbench import attribute
from chipbench.tests.conftest import make_root

root = make_root(Path(tempfile.mkdtemp()))
out, report = attribute.attribute("tiny.sort.4chip", 2**31 + 7, check=True, root=root,
                                  require_tpu=False, use_compile_cache=False,
                                  log=lambda s: None)
print(json.dumps({"out": out, "report": report}))
"""


def test_attribute_a_traced_four_chip_run():
    got = json.loads(run_python(FOUR, devices=4, timeout=900).strip().splitlines()[-1])
    out, report = got["out"], got["report"]
    assert out["correct"], out["checks"]
    # host spans are read on any platform; the device planes only on a TPU
    spans = report["spans"]
    assert spans["repro.submit"]["count"] == 1 and spans["repro.finalize"]["count"] == 1
    assert spans["repro.prepare"]["count"] >= 1 and spans["repro.prepare"]["s"] > 0
    assert spans["repro.dispatch"]["count"] == spans["repro.readback"]["count"] >= 1
    assert report["scopes"]["equal"] and report["scopes"]["metadata_differs"]
