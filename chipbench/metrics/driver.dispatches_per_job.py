"""Mean chunk dispatches per traced job (`JobHandle.chunks`): each is one
program launch and one halt readback in `run_until_chunks`."""

LAYER, UNIT, MOVES, SOURCE = "chunk loop", "dispatches", "job_p50_s", "program_counter"


def read(ctx):
    return sum(h.chunks for h in ctx.handles) / len(ctx.handles)
