"""Seconds from process start to the window's first submit: JAX start-up,
data generation, the service, and the warm-up jobs that load (or, in a
fresh checkout, compile) every program the window runs."""

LAYER, UNIT, MOVES, SOURCE = None, "s", None, "host_clock"


def read(ctx):
    return ctx.setup_s
