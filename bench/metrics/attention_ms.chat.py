"""Device milliseconds per traced engine step of the ops under the model's
``attention`` scope (ln1, QKV, paged KV write and read, output, residual),
chat cells."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "attention")
