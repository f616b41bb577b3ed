"""Device milliseconds per traced engine step of the layer scan's own
per-layer slices of the stacked weights and KV pool, and its stacked
updates (under no model scope), chat cells."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.SCAN_COPY)
