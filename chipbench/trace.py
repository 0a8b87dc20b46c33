"""Reduce a profiler trace (`.xplane.pb`) to device time, idle gaps and spans.

A device plane is named `/device:TPU:<id>`; its `XLA Ops` line holds one
event per executed HLO instruction, named by the instruction's text
(`%sort.19 = f32[...] sort(...)`), with a start and a duration in ns. Ops
nest (a `while` holds its body's ops), so busy time is the union of the
intervals, and per-op sums take only leaves. Host planes (`/host:CPU`) hold
the benchmark's own `TraceAnnotation` spans and the runtime's events, on
the same clock as the devices.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

# ops by the text the trace prints for them
KEYSTREAM = re.compile(r"^%chacha20_xor_rows_coalesced[.\d]* = .*custom-call\(")
SORT = re.compile(r"^%sort[.\d]* = ")
# by the opcode: JAX names the instruction after its primitive
# (`%all_to_all.7 = f32[...] all-to-all(...)`)
ALL_TO_ALL = re.compile(r"^%\S+ = .* all-to-all\(")


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction's name: `%sort.19 = ...` -> `sort.19`."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)  # device id -> [Event], leaves only
    busy: dict = field(default_factory=dict)  # device id -> [(start, end)] merged
    spans: list = field(default_factory=list)  # benchmark spans [Event]
    host: list = field(default_factory=list)  # other host events [Event]

    @property
    def window(self) -> tuple[float, float]:
        """From the first benchmark span's start to the last one's end."""
        if not self.spans:
            raise ValueError("the trace holds no benchmark span")
        return min(s.start for s in self.spans), max(s.end for s in self.spans)


def _leaves(events: list[Event]) -> list[Event]:
    """Events that contain no other event of the same line."""
    events = sorted(events, key=lambda e: (e.start, -e.dur))
    parent = [False] * len(events)
    stack: list[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(events, parent) if not p]


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval of `merged` covers."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def from_profile(pd, devices=None) -> Trace:
    """Build a `Trace` from a `jax.profiler.ProfileData`.

    `devices`: the ids of the cell's devices; None keeps every TPU plane.
    """
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                tr.ops[dev] = _leaves(evs)
                tr.busy[dev] = merge((e.start, e.end) for e in evs)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    (tr.spans if e.name.startswith(SPAN_PREFIX) else tr.host).append(ev)
    return tr


def load(path, devices=None) -> Trace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        return from_profile(ProfileData.from_serialized_xspace(f.read()), devices)


def busy_s(tr: Trace) -> dict:
    """Seconds in which some op ran, per device, within the window."""
    lo, hi = tr.window
    return {d: sum(e - s for s, e in clip(iv, lo, hi)) / 1e9 for d, iv in tr.busy.items()}


def op_seconds(tr: Trace, pattern: re.Pattern) -> dict:
    """Per device, the summed duration of leaf ops whose text matches."""
    lo, hi = tr.window
    return {d: sum(e.dur for e in evs if lo <= e.start < hi and pattern.search(e.name)) / 1e9
            for d, evs in tr.ops.items()}


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ops that took most time, summed by instruction name over the
    cell's devices and divided by their number."""
    lo, hi = tr.window
    total: dict = defaultdict(float)
    for evs in tr.ops.values():
        for e in evs:
            if lo <= e.start < hi:
                total[e.op] += e.dur
    k = max(1, len(tr.ops))
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def _most_overlap(events, s: float, e: float):
    best, best_ov = None, 0.0
    for ev in events:
        ov = min(ev.end, e) - max(ev.start, s)
        # prefer the event that covers the gap most; on a tie the shorter,
        # which says more about what the host was doing
        if ov > best_ov or (best is not None and ov == best_ov and ev.dur < best.dur):
            best, best_ov = ev, ov
    return best if best_ov > 0 else None


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """The longest stretches in which no device of the cell ran an op,
    each named by the benchmark span it fell in and the host event that
    overlapped it most: `bench.wait | np.asarray(jax.Array)`."""
    lo, hi = tr.window
    merged = merge(iv for ivs in tr.busy.values() for iv in ivs)
    out = []
    for s, e in sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:n]:
        span = _most_overlap(tr.spans, s, e)
        host = _most_overlap(tr.host, s, e)
        label = (span.name if span else "no span") + " | " + (host.name if host else "no host event")
        out.append([label, (e - s) / 1e9])
    return out
