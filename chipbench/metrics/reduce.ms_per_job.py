"""Device time of the `reduce` layer per traced job, per device: the leaf
ops whose instructions the program traced inside its `reduce` scope, the
job's reduce function (`repro/obs.py`), by the runners' `op_layers()`."""

from chipbench import layers

LAYER, UNIT, MOVES, SOURCE = "reduce", "ms", "job_p50_s", "device_trace"


def read(ctx):
    return layers.layer_ms_per_job(ctx, "reduce")
