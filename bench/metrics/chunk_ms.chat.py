"""Device milliseconds per call of the jitted prefill-chunk step, chat cells."""
from bench.metrics import common


def read(ctx):
    return common.program_ms(ctx, "jit_chunk_insert")
