"""Device time of the `bucket_pack` layer per traced job, per device: the
leaf ops whose instructions the program traced inside its `bucket_pack`
scope (`repro/obs.py`), by the runners' `op_layers()`."""

from chipbench import layers

LAYER, UNIT, MOVES, SOURCE = "bucket_pack", "ms", "job_p50_s", "device_trace"


def read(ctx):
    return layers.layer_ms_per_job(ctx, "bucket_pack")
