"""The keystream kernel's share of its memory roofline, in %.

Bytes: each secure round launches the kernel twice (encrypt and decrypt),
and each launch reads and writes the shard's coalesced wire; the wire's
unpadded payload is computed from the job's shapes
(`jobs/<kind>.py::wire_payload_bytes`) and its executed rounds. The least
time is those bytes over the chip's HBM bandwidth (`peaks.json`), and the
share is that time over the kernel's device time. Memory is the only bound
taken: the peaks table holds no published integer (VPU) peak for ChaCha20's
add-rotate-xor work, so the share can only be overstated by what a compute
bound would add, never by the bytes.
"""

from chipbench import trace

LAYER, UNIT, MOVES, SOURCE = "keystream kernel", "%", "job_p50_s", "device_trace"
LAUNCHES_PER_ROUND = 2


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = trace.op_seconds(ctx.trace, trace.KEYSTREAM)
    if not t or not any(t.values()):
        return None
    job, n = ctx.cell.job, int(ctx.traffic["n"])
    per_round = job.wire_payload_bytes(ctx.config, n, ctx.n_shards)
    rounds = sum(job.rounds(r) for r in ctx.results)
    nbytes = rounds * LAUNCHES_PER_ROUND * 2 * per_round  # read + write
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(t.values()) / len(t))
