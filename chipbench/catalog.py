"""Find a cell's pieces by name: configuration, traffic, job kind, metrics.

Nothing here knows any particular cell. A later change adds a cell, a
configuration, a traffic mix or a metric by adding a file and an entry in
`BENCHMARK.json`, and edits no file that is already there.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CatalogError(ValueError):
    pass


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CatalogError(f"missing file {path}") from None


def _load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files have dots in their names)."""
    if not path.is_file():
        raise CatalogError(f"missing file {path}")
    name = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str  # "end_to_end" | "per_layer"
    entry: dict
    reader: ModuleType

    def applies_to(self, cell: str) -> bool:
        cells = self.entry.get("workloads")
        return cells is None or cell in cells


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    job: ModuleType
    metrics: tuple  # of Metric, those this cell reports


class Catalog:
    """The benchmark as `BENCHMARK.json` under `root` declares it."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / "chipbench"
        self.index = _load_json(self.root / "BENCHMARK.json")

    def config(self, name: str) -> dict:
        for entry in self.index["configs"]:
            if entry["name"] == name:
                cfg = _load_json(self.root / entry["file"])
                if cfg.get("name") != name:
                    raise CatalogError(f"{entry['file']} names {cfg.get('name')!r}, not {name!r}")
                return cfg
        raise CatalogError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(self.dir / "traffic" / f"{name}.json")

    def job(self, kind: str) -> ModuleType:
        return _load_module(self.dir / "jobs" / f"{kind}.py")

    def metrics(self, kind: str) -> list[Metric]:
        out = []
        for entry in self.index[kind]:
            reader = _load_module(self.dir / "metrics" / f"{entry['name']}.py")
            out.append(Metric(entry["name"], entry["unit"], kind, entry, reader))
        return out

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.index["workloads"]]

    def cell(self, name: str, *, traced: bool) -> Cell:
        for w in self.index["workloads"]:
            if w["name"] == name:
                break
        else:
            raise CatalogError(f"no workload {name!r} in BENCHMARK.json "
                               f"(cells: {', '.join(self.cell_names())})")
        config = self.config(w["config"])
        kind = "per_layer" if traced else "end_to_end"
        metrics = tuple(m for m in self.metrics(kind) if m.applies_to(name))
        return Cell(name=name, chips=int(w["chips"]), config=config,
                    traffic=self.traffic(w["traffic"]), job=self.job(config["job"]),
                    metrics=metrics)

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.dir / "peaks.json")["devices"]
        if device_kind not in table:
            raise CatalogError(f"no published peaks for device kind {device_kind!r} "
                               f"in chipbench/peaks.json (known: {', '.join(table)})")
        return table[device_kind]
