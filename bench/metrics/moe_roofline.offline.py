"""Share of its roofline reached by the fused MoE kernel, offline cells."""
from bench.metrics import common


def read(ctx):
    return common.moe_roofline(ctx)
