"""Device time of the shuffle's `all-to-all` ops per traced job, per device
(only on a mesh of more than one chip; one chip runs no collective)."""

from chipbench import trace

LAYER, UNIT, MOVES, SOURCE = "all_to_all", "ms", "job_p50_s", "device_trace"


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.op_seconds(ctx.trace, trace.ALL_TO_ALL)
    if not t or not any(t.values()):
        return None
    return 1e3 * sum(t.values()) / len(t) / len(ctx.handles)
