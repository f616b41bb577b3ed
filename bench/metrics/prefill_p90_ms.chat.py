"""p90 of the time from the first prefill chunk's dispatch to the first
token, from the engine's own lifecycle stamps, over the requests submitted
in the window, chat cells."""
from bench import scopes


def read(ctx):
    return scopes.prefill_p90_ms(ctx)
