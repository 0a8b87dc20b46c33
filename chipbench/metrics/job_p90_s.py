"""90th percentile of the job times that `job_p50_s` takes the median of.
Only cells that complete 100 jobs or more in a window report it."""

from chipbench.stats import percentile

LAYER, UNIT, MOVES, SOURCE = None, "s", None, "host_clock"


def read(ctx):
    return percentile([r.latency_s for r in ctx.records], 90)
