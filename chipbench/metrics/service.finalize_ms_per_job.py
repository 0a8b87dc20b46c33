"""Host time of the program's `repro.finalize` spans per traced job: the
scheduler thread reading the job's result back and assembling the answer."""

from chipbench import layers

LAYER, UNIT, MOVES, SOURCE = "service", "ms", "job_p50_s", "device_trace"


def read(ctx):
    return layers.span_ms_per_job(ctx, "repro.finalize")
