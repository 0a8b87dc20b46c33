"""Device time of XLA `sort` ops per traced job, per device: the stable
argsort in `bucket_pack` and the reducer's sort. `searchsorted` runs as a
`while` loop of fusions and is not counted here."""

from chipbench import trace

LAYER, UNIT, MOVES, SOURCE = "bucket_pack and reduce", "ms", "job_p50_s", "device_trace"


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.op_seconds(ctx.trace, trace.SORT)
    if not t or not any(t.values()):
        return None
    return 1e3 * sum(t.values()) / len(t) / len(ctx.handles)
