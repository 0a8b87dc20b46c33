"""Median admission wait of the traced jobs: `JobHandle.queue_s`, from the
service's submit stamp to the scheduler thread starting the job."""

import statistics

LAYER, UNIT, MOVES, SOURCE = "service", "ms", "job_p50_s", "program_counter"


def read(ctx):
    waits = [h.queue_s for h in ctx.handles if h.queue_s is not None]
    return 1e3 * statistics.median(waits) if waits else None
