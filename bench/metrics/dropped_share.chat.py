"""Share of sub-pairs the 2T-Drop policy dropped over the traced steps,
from the program's own counters (repro.obs: dropped / (kept_full +
kept_major + dropped)), chat cells. The counters also count the rows of
idle decode slots and of a chunk's padding."""


def read(ctx):
    kf, km, dr = ctx.counts
    total = kf + km + dr
    return 100.0 * dr / total if total else None
