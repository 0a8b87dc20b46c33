"""Device time of the Pallas ChaCha20 kernel per traced job, per device:
the `chacha20_xor_rows_coalesced` custom calls (encrypt and decrypt)."""

from chipbench import trace

LAYER, UNIT, MOVES, SOURCE = "keystream kernel", "ms", "job_p50_s", "device_trace"
KERNEL = trace.KEYSTREAM


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.op_seconds(ctx.trace, KERNEL)
    if not t or not any(t.values()):
        return None
    return 1e3 * sum(t.values()) / len(t) / len(ctx.handles)
