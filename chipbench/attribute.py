"""Split one traced run's device time by layer and its device-idle time by span.

    python3 chipbench/attribute.py --workload <cell> --seed <n> [--keep-trace DIR] [--check-scopes]

Runs the cell as `run.py --trace 1` does and prints that result line, then
one more JSON line, every time per traced job and per device:

- `layers`: seconds of the leaf ops inside `jit_run` modules by the layer
  scope the program traced them under (`layers.py`); `None` is no scope,
  `-` an op no runner's program holds, `?` one the runners disagree on;
- `idle`: the window's device-idle seconds by the program's host span they
  fall in, scheduler spans first (`SPAN_ORDER`), and the longest stretches
  in none, each named by the host events that overlapped it most;
- `spans`: count and seconds of each `repro.*` span, and `job_s` each
  traced job's time, submit to result;
- with `--check-scopes`: whether the first runner's compiled program, its
  metadata stripped, equals the same program traced with every
  `jax.named_scope` a no-op.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, layers, trace  # noqa: E402
from chipbench.catalog import Catalog, Metric  # noqa: E402

SPAN_ORDER = ("repro.dispatch", "repro.readback", "repro.prepare", "repro.finalize",
              "repro.submit")
MODULE_LINE = "XLA Modules"


class _Capture(Catalog):
    """The catalog with one more per-layer reader that keeps the run's
    context and reports nothing."""

    def __init__(self, box: dict, root: Path = ROOT):
        super().__init__(root)
        self.box = box

    def cell(self, name, *, traced):
        cell = super().cell(name, traced=traced)
        keep = types.SimpleNamespace(read=lambda ctx: self.box.update(ctx=ctx))
        return dataclasses.replace(
            cell, metrics=cell.metrics + (Metric("_capture", "", "per_layer", {}, keep),))


def _subtract(intervals, cover):
    """The parts of `intervals` outside the merged `cover`."""
    out = []
    for s, e in intervals:
        out += trace.gaps(cover, s, e)
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def module_intervals(xplane: Path, prefix: str = "jit_run") -> dict:
    """Per device id, the intervals of the modules whose name starts with
    `prefix`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xplane.read_bytes())
    out = {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULE_LINE:
                out[int(m.group(1))] = trace.merge(
                    (e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    if e.name.startswith(prefix))
    return out


def layer_split(tr: trace.Trace, layer_of: dict, modules: dict) -> dict:
    lo, hi = tr.window
    out: dict = defaultdict(float)
    for dev, evs in tr.ops.items():
        inside = modules.get(dev, [])
        for e in evs:
            if lo <= e.start < hi and any(s <= e.start < t for s, t in inside):
                out[str(layer_of.get(layers.op_key(e.name), layers.UNMATCHED))] += e.dur / 1e9
    return dict(out)


def idle_split(tr: trace.Trace, n: int = 6) -> tuple[dict, list]:
    lo, hi = tr.window
    by_name = {name: trace.merge((e.start, e.end) for e in layers.spans(tr, name))
               for name in SPAN_ORDER}
    others = [e for e in tr.host if not e.name.startswith("repro.")]
    out: dict = defaultdict(float)
    left: list = []
    for busy in tr.busy.values():
        idle = trace.gaps(busy, lo, hi)
        out["idle"] += _length(idle) / 1e9
        for name in SPAN_ORDER:
            rest = _subtract(idle, by_name[name])
            out[name] += (_length(idle) - _length(rest)) / 1e9
            idle = rest
        out["none"] += _length(idle) / 1e9
        left += idle
    named = []
    for s, e in sorted(left, key=lambda g: g[0] - g[1])[:n]:
        bench = trace._most_overlap(tr.spans, s, e)
        host = trace._most_overlap(others, s, e)
        named.append([f"{bench.name if bench else 'no span'} | "
                      f"{host.name if host else 'no host event'}", (e - s) / 1e9])
    return dict(out), named


def check_scopes(ctx) -> dict:
    """Compile the first runner's program again, traced with every layer
    scope a no-op, and compare the two texts with metadata stripped."""
    import jax
    from repro import obs
    from repro.compat import make_mesh
    from repro.serve.service import RunnerCache, SecureJobService

    runner = ctx.handles[0].runners[0]
    scoped = runner.jitted.lower(*runner.arg_specs).compile().as_text()
    real, key_flag = jax.named_scope, "jax_compilation_cache_include_metadata_in_key"
    had_metadata_key = getattr(jax.config, key_flag)
    jax.named_scope = lambda name: contextlib.nullcontext()
    # the compile cache's key leaves metadata out: put it in, so that the
    # bare program is compiled and not loaded as the scoped one
    jax.config.update(key_flag, True)
    try:
        mesh = make_mesh((ctx.cell.chips,), ("data",), devices=jax.devices()[: ctx.cell.chips])
        data = harness.make_datasets(ctx.cell, 0)[0]
        with SecureJobService(mesh, secure=harness.secure_config(), cache=RunnerCache()) as svc:
            # one round: the first chunk's program, the one compared
            job = ctx.cell.job.submit(svc, data, dict(ctx.cell.config, max_rounds=1))
            job.result()
        bare_runner = job.runners[0]
        bare = bare_runner.jitted.lower(*bare_runner.arg_specs).compile().as_text()
    finally:
        jax.named_scope = real
        jax.config.update(key_flag, had_metadata_key)
    a, b = obs.strip_metadata(scoped), obs.strip_metadata(bare)
    diff = [(x, y) for x, y in zip(a.splitlines(), b.splitlines()) if x != y][:5]
    return {"equal": a == b, "metadata_differs": scoped != bare,
            "lines": len(a.splitlines()), "first_differences": diff}


def attribute(cell: str, seed: int, *, keep_trace: str | None = None,
              check: bool = False, root: Path = ROOT, **run_kw) -> tuple[dict, dict]:
    """One traced run: its result line's object and the split."""
    box: dict = {}
    keep = keep_trace or tempfile.mkdtemp(prefix="chipbench-attribute-")
    out = harness.run(cell, seed, 0.0, True, catalog=_Capture(box, root), trace_dir=keep,
                      **run_kw)
    ctx = box["ctx"]
    tr, jobs = ctx.trace, len(ctx.handles)
    n_dev = max(1, len(tr.ops))
    xplane = sorted(Path(keep).glob("*.xplane.pb"))[-1]
    layer_of = layers.layer_map(ctx.handles) or {}
    per = {k: v / jobs / n_dev for k, v in
           layer_split(tr, layer_of, module_intervals(xplane)).items()}
    idle, left = idle_split(tr)
    spans = {name: {"count": len(found) / jobs, "s": sum(e.dur for e in found) / 1e9 / jobs}
             for name in SPAN_ORDER for found in [layers.spans(tr, name)]}
    report = {"jobs": jobs, "devices": n_dev,
              "job_s": sorted(r.latency_s for r in ctx.records),
              "layers": dict(sorted(per.items(), key=lambda kv: -kv[1])),
              "layers_total_s": sum(per.values()),
              "idle": {k: v / jobs / n_dev for k, v in idle.items()},
              "idle_left": left, "spans": spans}
    if check:
        report["scopes"] = check_scopes(ctx)
    return out, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep-trace", metavar="DIR")
    ap.add_argument("--check-scopes", action="store_true")
    args = ap.parse_args(argv)
    try:
        out, report = attribute(args.workload, args.seed, keep_trace=args.keep_trace,
                                check=args.check_scopes, t_process=T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.report(out)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
