"""Time by the program's own layers: its layer scopes and its host spans.

The program names its layers itself (`repro/obs.py`). On the device, each
runner a job dispatched offers `op_layers()`: every instruction of its
compiled program mapped to the innermost layer scope it was traced under.
A trace op is matched to that map by its key, the instruction's name,
result shape and opcode: the trace prints operand shapes that the compiled
text leaves out, and layouts are dropped on both sides. On the host, the
scheduler's `repro.*` spans land in the trace's host events.

Against a program that has neither (no `runners` on its job handles, no
`repro.*` span), every reader returns None.
"""

from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace

AMBIGUOUS = "?"  # one key that the runners map to different layers
UNMATCHED = "-"  # a trace op whose key no runner's program holds
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/|\s")


def op_key(text: str) -> str:
    """`%fusion.29 = s32[8]{0:T(1024)} fusion(s32[8]{0} %p), ...` ->
    `%fusion.29 = s32[8] fusion`."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    if rest.startswith("("):  # a tuple shape: up to its closing parenthesis
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, rest = rest[: i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    return f"{name} = {_LAYOUT.sub('', shape)} {opcode}"


def layer_map(handles) -> dict | None:
    """Op key -> layer (None: no layer scope) over the runners the jobs
    dispatched; None when no job holds a runner that can say."""
    runners = {id(r): r for h in handles for r in getattr(h, "runners", ())}
    out: dict = {}
    for r in runners.values():
        for text, layer in (r.op_layers() or {}).items():
            key = op_key(text)
            out[key] = layer if out.get(key, layer) == layer else AMBIGUOUS
    return out or None


def layer_seconds(tr: trace.Trace, layers: dict) -> dict:
    """Seconds of leaf ops in the window by layer (None, `AMBIGUOUS`,
    `UNMATCHED` included), summed over the devices."""
    lo, hi = tr.window
    out: dict = defaultdict(float)
    for evs in tr.ops.values():
        for e in evs:
            if lo <= e.start < hi:
                out[layers.get(op_key(e.name), UNMATCHED)] += e.dur / 1e9
    return dict(out)


def layer_ms_per_job(ctx, layer: str) -> float | None:
    """Device time of `layer` per traced job, per device."""
    if ctx.trace is None or not ctx.trace.ops or not ctx.handles:
        return None
    layers = layer_map(ctx.handles)
    if layers is None:
        return None
    s = layer_seconds(ctx.trace, layers).get(layer, 0.0)
    return 1e3 * s / len(ctx.trace.ops) / len(ctx.handles) if s else None


def spans(tr: trace.Trace, name: str) -> list:
    """The program's host spans called `name` that start in the window."""
    lo, hi = tr.window
    return [e for e in tr.host if e.name == name and lo <= e.start < hi]


def span_ms_per_job(ctx, name: str) -> float | None:
    """Summed duration of the `name` spans per traced job."""
    if ctx.trace is None or not ctx.handles:
        return None
    found = spans(ctx.trace, name)
    return sum(e.dur for e in found) / 1e6 / len(ctx.handles) if found else None
