"""Needed model FLOPs over step wall time, share of the bf16 peak, chat cells."""
from bench.metrics import common


def read(ctx):
    return common.mfu(ctx)
