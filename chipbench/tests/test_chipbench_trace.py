"""The trace reduction: on hand-made intervals, and on a trace from the chip.

`testdata/chip_trace.xplane.pb.gz` is a profiler trace recorded on one TPU
v5 lite: a secure k-means job and a secure sort job of 2^14 points and keys
through `SecureJobService`, between a `bench.submit` and a `bench.wait`
span. The test scans the raw trace by itself and checks the reduction's
numbers against that scan.
"""

import gzip
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.catalog import Catalog

DATA = Path(__file__).resolve().parents[1] / "testdata" / "chip_trace.xplane.pb.gz"


def test_leaves_merge_and_gaps():
    E = trace.Event
    evs = [E("%while.1 = ...", 0, 100), E("%fusion.1 = ...", 10, 20), E("%sort.2 = ...", 30, 60),
           E("%fusion.3 = ...", 150, 170)]
    assert [e.op for e in trace._leaves(evs)] == ["fusion.1", "sort.2", "fusion.3"]
    merged = trace.merge([(0, 100), (10, 20), (150, 170), (165, 180)])
    assert merged == [(0, 100), (150, 180)]
    assert trace.gaps(merged, -10, 200) == [(-10, 0), (100, 150), (180, 200)]
    assert trace.gaps(merged, 20, 160) == [(100, 150)]


def test_op_patterns_match_the_compiled_text():
    """Op texts as the TPU compiler prints them for the sort's programs."""
    a2a = ("%all_to_all.7 = f32[4,4096,1]{1,2,0:T(1,128)S(1)} all-to-all(%bitcast.2), "
           "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
    fusion = ("%fusion.85 = (f32[4,4096]{1,0}, s32[4]{0}) fusion(%all_to_all.7, %p), "
              "kind=kLoop, calls=%fused_computation.85")
    ks = "%chacha20_xor_rows_coalesced.3 = u32[1,4096]{1,0} custom-call(%a, %b)"
    sort = "%sort.71 = (f32[8]{0}, s32[8]{0}) sort(%x, %y), dimensions={0}"
    hits = {name: [t for t in (a2a, fusion, ks, sort) if pat.search(t)]
            for name, pat in [("a2a", trace.ALL_TO_ALL), ("ks", trace.KEYSTREAM),
                              ("sort", trace.SORT)]}
    assert hits == {"a2a": [a2a], "ks": [ks], "sort": [sort]}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))
    return pd, trace.from_profile(pd)


def _raw(pd, prefix):
    """Durations of the device's op events whose text starts with `prefix`,
    and the raw span window, by a plain scan of the planes."""
    total, spans = 0, []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops" and e.name.startswith(prefix):
                    total += e.duration_ns
                if e.name.startswith("bench."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
    return total / 1e9, (min(s for s, _ in spans), max(e for _, e in spans))


def test_reduction_of_a_chip_trace(recorded):
    pd, tr = recorded
    assert list(tr.ops) == [0]
    ks, window = _raw(pd, "%chacha20_xor_rows_coalesced")
    assert tr.window == window
    assert ks > 0 and trace.op_seconds(tr, trace.KEYSTREAM)[0] == pytest.approx(ks)
    sort_s, _ = _raw(pd, "%sort")
    assert sort_s > 0 and trace.op_seconds(tr, trace.SORT)[0] == pytest.approx(sort_s)
    assert trace.op_seconds(tr, trace.ALL_TO_ALL)[0] == 0.0  # one chip, no collective

    lo, hi = tr.window
    busy = trace.busy_s(tr)[0]
    idle = sum(e - s for s, e in trace.gaps(tr.busy[0], lo, hi)) / 1e9
    assert 0 < busy < (hi - lo) / 1e9
    assert busy + idle == pytest.approx((hi - lo) / 1e9, abs=1e-9)

    top = trace.top_ops(tr)
    assert 0 < len(top) <= 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = trace.idle_gaps(tr)
    assert 0 < len(gaps) <= 10 and all(g[0].startswith("bench.") for g in gaps)
    assert gaps[0][1] == pytest.approx(max(e - s for s, e in trace.gaps(tr.busy[0], lo, hi)) / 1e9)


def test_metric_readers_on_a_chip_trace(recorded, tiny_root):
    _, tr = recorded
    cat = Catalog(tiny_root)
    readers = {m.name: m.reader for m in cat.metrics("per_layer")}

    class Ctx:
        trace = tr
        handles = [object(), object()]  # two jobs were traced

    lo, hi = tr.window
    idle = readers["device.idle_pct"].read(Ctx)
    assert idle == pytest.approx(100 * (1 - trace.busy_s(tr)[0] / ((hi - lo) / 1e9)))
    assert 0 < idle < 100
    ks = readers["keystream.ms_per_job"].read(Ctx)
    assert ks == pytest.approx(1e3 * trace.op_seconds(tr, trace.KEYSTREAM)[0] / 2)
    assert readers["all_to_all.ms_per_job"].read(Ctx) is None  # nothing to read


def test_roofline_share_from_bytes_and_kernel_time(recorded, tiny_root):
    _, tr = recorded
    cat = Catalog(tiny_root)
    cell = cat.cell("tiny.sort", traced=True)
    reader = {m.name: m.reader for m in cat.metrics("per_layer")}["keystream_roofline"]

    class Ctx:
        trace = tr
        config, traffic, n_shards = cell.config, {"n": 1 << 14}, 1
        results = [{"rounds": 1}]
        peaks = cat.peaks("TPU v5 lite")

    Ctx.cell = cell
    ks = trace.op_seconds(tr, trace.KEYSTREAM)[0]
    nbytes = 1 * 2 * 2 * cell.job.wire_payload_bytes(cell.config, 1 << 14, 1)
    want = 100 * nbytes / 819e9 / ks
    assert reader.read(Ctx) == pytest.approx(want)
    assert 0 < want < 100
