"""Input MiB of every completed job (the points or the keys array) over the
time from the window's first submit to its last completion after the drain."""

from chipbench.stats import drained_rate

LAYER, UNIT, MOVES, SOURCE = None, "MiB/s", None, "host_clock"


def read(ctx):
    return drained_rate(ctx.records, scale=1 << 20)
