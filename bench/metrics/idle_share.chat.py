"""Share of the traced window in which the chip ran nothing, chat cells."""
from bench.metrics import common


def read(ctx):
    return common.idle_share(ctx)
