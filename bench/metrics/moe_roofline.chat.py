"""Share of its roofline reached by the fused MoE kernel, chat cells."""
from bench.metrics import common


def read(ctx):
    return common.moe_roofline(ctx)
