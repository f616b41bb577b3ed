"""Engine span tracing, on the profiler's clock.

``SpanTracer.span(name, **args)`` opens a
``jax.profiler.TraceAnnotation(f"engine_{name}", **args)`` around its
body, so every engine phase (``engine_step``, ``engine_admit``,
``engine_prefill_chunk``, ``engine_decode``, ``engine_readback``,
``engine_emit``, ...) lands in the host plane of a profiler capture, on
the same clock as the device's XLA ops. While no profiler runs, an
annotation is one small object and records nothing.

The tracer can also keep its own in-memory buffer of the same spans
(``time.perf_counter`` timestamps, bounded), exportable as Chrome-trace
(Perfetto / chrome://tracing) JSON. That buffer is off unless the engine
is built with ``trace=True`` (the serve CLI's ``--trace-out``). Nothing
here touches device arrays, so no span adds a sync to the jitted path.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List

import jax


class SpanTracer:
    """Profiler annotations for engine spans, plus an optional bounded
    Chrome-trace buffer (``enabled``) of the same spans and instants."""

    def __init__(self, *, enabled: bool = False, max_events: int = 100_000):
        self.enabled = enabled
        self.max_events = int(max_events)
        self._events: List[Dict[str, Any]] = []
        self._origin = time.perf_counter()
        self._dropped = 0

    def _push(self, ev: Dict[str, Any]) -> None:
        if len(self._events) >= self.max_events:
            self._dropped += 1
            return
        self._events.append(ev)

    @contextmanager
    def span(self, name: str, **args: Any):
        """``engine_<name>`` profiler annotation around the body; with the
        buffer on, also a complete-duration ("X") event."""
        with jax.profiler.TraceAnnotation(f"engine_{name}", **args):
            if not self.enabled:
                yield
                return
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                self._push({"name": name, "ph": "X",
                            "ts": (t0 - self._origin) * 1e6,
                            "dur": (t1 - t0) * 1e6, "args": args})

    def instant(self, name: str, **args: Any) -> None:
        """A zero-length ``engine_<name>`` annotation; with the buffer on,
        also a zero-duration ("i") marker event."""
        with jax.profiler.TraceAnnotation(f"engine_{name}", **args):
            pass
        if self.enabled:
            self._push({"name": name, "ph": "i",
                        "ts": (time.perf_counter() - self._origin) * 1e6,
                        "s": "t", "args": args})

    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def chrome_trace(self, *, pid: int = 1, tid: int = 1) -> Dict[str, Any]:
        """Chrome-trace JSON object (``traceEvents`` array format)."""
        out = []
        for ev in self._events:
            ce = dict(ev)
            ce.setdefault("pid", pid)
            ce.setdefault("tid", tid)
            ce.setdefault("cat", "engine")
            out.append(ce)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self._dropped}}

    def write_chrome_trace(self, path: str, **kw: Any) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(**kw), f)
