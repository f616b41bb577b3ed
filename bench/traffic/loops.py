"""Open- and closed-loop clients of an engine's ``submit``/``step`` API.

The benchmark stamps every token itself, on the host clock, right after
the ``step()`` that produced it, by comparing each live request's token
count with the count it had before. A time to first token runs from the
request's scheduled arrival (open loop) or its submission (closed loop) to
that stamp, so a late generator or a stalled step counts against the
system. Each host phase of the loop sits in a profiler annotation
(``bench_submit``, ``bench_step``, ``bench_tokens``, ``bench_wait``) so a
traced run can say what the host was doing while the device idled.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax

from .generator import Request


@dataclasses.dataclass
class Served:
    req: Request
    uid: int
    sent_s: float                       # when it was submitted
    due_s: float                        # when it was due (arrival / sent)
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def ttft_s(self) -> Optional[float]:
        return self.stamps[0] - self.due_s if self.stamps else None


@dataclasses.dataclass
class StepLog:
    t0: float
    t1: float
    prefill_tokens: int                 # prompt tokens this step prefilled


@dataclasses.dataclass
class Window:
    seconds: float
    served: List[Served]
    steps: List[StepLog]
    late_s: List[float]                 # how late each open-loop send was
    end_s: float = 0.0                  # when the loop stopped stepping

    def in_window(self) -> List[Served]:
        return [s for s in self.served if s.due_s < self.seconds]

    def tokens_in_window(self) -> int:
        return sum(1 for s in self.served for t in s.stamps
                   if t <= self.seconds)

    def prefill_in_window(self) -> int:
        return sum(st.prefill_tokens for st in self.steps
                   if st.t1 <= self.seconds)


class Loop:
    """Drives ``engine`` and stamps tokens. ``gen_for(n_out)`` builds the
    engine's per-request generation settings; ``around_step`` (optional)
    is called as ``around_step(phase, loop)`` with phase "before"/"after"
    around every step, for a traced run's accounting."""

    def __init__(self, engine, gen_for: Callable[[int], object], *,
                 around_step: Optional[Callable] = None):
        self.engine = engine
        self.gen_for = gen_for
        self.around_step = around_step
        self.served: List[Served] = []
        self.live: Dict[int, Served] = {}
        self.steps: List[StepLog] = []
        self.late_s: List[float] = []
        self.t0 = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, req: Request, due_s: float) -> Served:
        with jax.profiler.TraceAnnotation("bench_submit"):
            uid = self.engine.submit(req.prompt, self.gen_for(req.n_out))
            s = Served(req, uid, self.now(), due_s)
        self.served.append(s)
        self.live[uid] = s
        return s

    def step(self) -> List[Served]:
        """One engine step; returns the requests that finished in it."""
        if self.around_step is not None:
            self.around_step("before", self)
        n0 = self.engine.prefill_tokens
        t0 = self.now()
        with jax.profiler.TraceAnnotation("bench_step"):
            self.engine.step()
        t1 = self.now()
        self.steps.append(StepLog(t0, t1, self.engine.prefill_tokens - n0))
        finished = []
        with jax.profiler.TraceAnnotation("bench_tokens"):
            for uid, s in list(self.live.items()):
                n = len(self.engine.result(uid).tokens)
                if n > len(s.stamps):
                    s.stamps.extend([t1] * (n - len(s.stamps)))
                if n >= s.req.n_out:
                    s.done = True
                    del self.live[uid]
                    finished.append(s)
        if self.around_step is not None:
            self.around_step("after", self)
        return finished

    def wait(self, until_s: float) -> None:
        with jax.profiler.TraceAnnotation("bench_wait"):
            dt = until_s - self.now()
            if dt > 0:
                time.sleep(min(dt, 0.01))

    # -- the two loops -------------------------------------------------------

    def run_open(self, requests: List[Request], seconds: float,
                 grace_s: float) -> Window:
        """Send each request at its scheduled arrival for ``seconds``; then
        keep stepping, sending nothing more, until every request that
        arrived has its first token (or ``grace_s`` has passed)."""
        pending = collections.deque(sorted(requests,
                                           key=lambda r: r.arrival_s))
        self.t0 = time.perf_counter()
        while self.now() < seconds:
            while pending and pending[0].arrival_s <= self.now():
                r = pending.popleft()
                self.late_s.append(self.now() - r.arrival_s)
                self.submit(r, r.arrival_s)
            if self.live:
                self.step()
            elif pending:
                self.wait(min(pending[0].arrival_s, seconds))
            else:
                self.wait(seconds)
        while (any(not s.stamps for s in self.live.values())
               and self.now() < seconds + grace_s):
            self.step()
        return Window(seconds, self.served, self.steps, self.late_s,
                      self.now())

    def run_closed(self, requests: List[Request], clients: int,
                   stagger_s: float, seconds: float) -> Window:
        """Each client sends its next request as soon as its last one is
        done; client i sends its first at ``i * stagger_s``."""
        queues = [collections.deque(r for r in requests if r.client == c)
                  for c in range(clients)]
        start = collections.deque((c * stagger_s, c) for c in range(clients))
        self.t0 = time.perf_counter()
        while self.now() < seconds:
            while start and start[0][0] <= self.now():
                _, c = start.popleft()
                self._send_next(queues[c])
            if self.live:
                for s in self.step():
                    if self.now() < seconds:
                        self._send_next(queues[s.req.client])
            else:
                self.wait(start[0][0] if start else seconds)
        return Window(seconds, self.served, self.steps, self.late_s,
                      self.now())

    def _send_next(self, queue) -> None:
        if not queue:
            raise RuntimeError("a closed-loop client ran out of requests "
                               "inside the window; raise "
                               "requests_per_client in the mix")
        self.submit(queue.popleft(), self.now())
