"""Per-layer metric readers: ``<metric name>.py`` defines ``read(ctx)``,
which returns the metric's value or None where the traced run holds
nothing to read. ``ctx`` is built by ``bench/run.py`` (see ``Context``
there); ``common`` holds what several readers share."""
