"""Device milliseconds per call of the jitted decode step, offline cells."""
from bench.metrics import common


def read(ctx):
    return common.program_ms(ctx, "jit_decode")
