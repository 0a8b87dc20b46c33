"""Host time of the program's `repro.prepare` spans per traced job: the
scheduler thread padding the job's input, building its initial state and
placing both on the devices, before the first dispatch."""

from chipbench import layers

LAYER, UNIT, MOVES, SOURCE = "service", "ms", "job_p50_s", "device_trace"


def read(ctx):
    return layers.span_ms_per_job(ctx, "repro.prepare")
