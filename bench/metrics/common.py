"""Arithmetic shared by the per-layer readers."""
from __future__ import annotations

from bench import work


def program_ms(ctx, name: str):
    """Mean device milliseconds per call of program ``name``."""
    secs, calls = ctx.programs.get(name, (0.0, 0))
    return 1e3 * secs / calls if calls else None


def moe_roofline(ctx):
    """The MoE kernel's share of its roofline: the least time the needed
    MoE work takes at the chip's peaks, over the kernel's device time."""
    ns, calls = ctx.kernel
    if not calls or not ctx.steps:
        return None
    need = ctx.family.moe_needed(ctx.s, ctx.p, ctx.steps,
                                  work.kept_share(ctx.counts))
    return work.roofline_share(need["flops"], need["bytes"], ns * 1e-9,
                               ctx.peaks)


def mfu(ctx):
    """Needed model operations of the traced steps over their summed host
    wall time, as a share of the chip's bf16 peak."""
    wall = sum(r["wall_s"] for r in ctx.steps)
    if not wall:
        return None
    flops = ctx.family.model_flops(ctx.s, ctx.steps,
                                   work.kept_share(ctx.counts))
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops"])


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the chip."""
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
