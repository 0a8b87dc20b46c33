"""The benchmark is found by name: new files add cells, configs and metrics."""

import json
import re

import pytest

from chipbench.catalog import ROOT, Catalog, CatalogError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_files_alone_add_a_config_a_cell_and_a_metric(tiny_root):
    bench = tiny_root / "chipbench"
    cfg = json.loads((bench / "configs" / "npb_is_a.json").read_text())
    cfg.update(name="npb_is_w", max_key=1 << 16)
    (bench / "configs" / "npb_is_w.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "closed1.n1M.json").write_text(json.dumps(
        {"n": 1 << 20, "clients": 1, "datasets": 1, "warmup_datasets": 1, "trace_jobs": 1}))
    (bench / "metrics" / "jobs.count.py").write_text(
        'LAYER, UNIT, MOVES, SOURCE = "service", "jobs", "job_p50_s", "program_counter"\n'
        "def read(ctx):\n    return len(ctx.records)\n")
    index = json.loads((tiny_root / "BENCHMARK.json").read_text())
    index["configs"].append({"name": "npb_is_w", "source": "NPB IS class W", "reduced": [],
                             "file": "chipbench/configs/npb_is_w.json", "why": "a test"})
    index["workloads"].append({"name": "sort.1M", "config": "npb_is_w", "traffic": "closed1.n1M",
                               "chips": 1, "why": "a test"})
    index["per_layer"].append({"name": "jobs.count", "unit": "jobs", "better": "higher",
                               "source": "program_counter", "layer": "service",
                               "moves": "job_p50_s", "workloads": ["sort.1M"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(index))

    cat = Catalog(tiny_root)
    assert "sort.1M" in cat.cell_names()
    cell = cat.cell("sort.1M", traced=True)
    assert cell.config["max_key"] == 1 << 16 and cell.traffic["n"] == 1 << 20
    assert cell.job.__name__.endswith("sort")
    names = [m.name for m in cell.metrics]
    assert "jobs.count" in names and "keystream_roofline" not in names

    class Ctx:
        records = [1, 2, 3]

    assert dict((m.name, m) for m in cell.metrics)["jobs.count"].reader.read(Ctx) == 3
    # the new metric is not reported where its workloads list leaves it out
    assert "jobs.count" not in [m.name for m in cat.cell("sort.8M", traced=True).metrics]


def test_unknown_names_and_devices_are_errors(tiny_root):
    cat = Catalog(tiny_root)
    with pytest.raises(CatalogError, match="no workload"):
        cat.cell("no.such.cell", traced=False)
    assert cat.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(CatalogError, match="no published peaks"):
        cat.peaks("cpu")


def test_benchmark_json_is_well_formed():
    index = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(index) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert index["paths"] == ["chipbench"]
    assert 1 <= index["run_seconds"] <= 51
    cat = Catalog()
    e2e = {m["name"]: m for m in index["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in index["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    used = {w["config"] for w in index["workloads"]}
    assert used == {c["name"] for c in index["configs"]}
    for c in index["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["source"]) <= 200
        assert cat.config(c["name"])["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in index["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in index["workloads"]) <= max(1, len(pairs) // 2)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in index[k]]
    assert all(NAME.match(n) for n in names)
    for w in index["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = cat.cell(w["name"], traced=False)
        reported = {m.name for m in cell.metrics}
        assert "setup_s" in reported and len(reported) >= 2
        per_layer = cat.cell(w["name"], traced=True).metrics
        assert per_layer
        assert all(m.entry["moves"] in reported for m in per_layer)
    for kind in ("end_to_end", "per_layer"):
        for m in cat.metrics(kind):
            assert UNIT.match(m.unit) and m.entry["better"] in ("lower", "higher")
            r = m.reader
            assert (r.UNIT, r.SOURCE) == (m.unit, m.entry["source"]), m.name
            if kind == "per_layer":
                assert (r.LAYER, r.MOVES) == (m.entry["layer"], m.entry["moves"]), m.name
    assert len(json.dumps(index)) < 64 * 1024
