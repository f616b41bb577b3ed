"""p90 of the wait from submission to the first prefill chunk's dispatch,
from the engine's own lifecycle stamps (``Result.prefill_start_s``), over
the requests submitted in the window, chat cells."""
from bench import scopes


def read(ctx):
    return scopes.queue_wait_p90_ms(ctx)
