"""Share of the traced window in which no op ran on a device, averaged
over the cell's devices: 1 - (union of `XLA Ops` intervals / window).
The window runs from the first traced submit to the last result."""

from chipbench import trace

LAYER, UNIT, MOVES, SOURCE = "device", "%", "input_MiB_per_s", "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy:
        return None
    lo, hi = ctx.trace.window
    busy = trace.busy_s(ctx.trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ((hi - lo) / 1e9))
