"""Device milliseconds per traced engine step of the ops under the model's
``moe`` scope (ln2, routing, dispatch, the fused kernel, combine), chat
cells."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "moe")
