"""Median job time: `submit_*` to `.result()` on the client's clock, over
every job completed in the window, the drain included."""

import statistics

LAYER, UNIT, MOVES, SOURCE = None, "s", None, "host_clock"


def read(ctx):
    return statistics.median(r.latency_s for r in ctx.records)
