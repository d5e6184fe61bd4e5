"""A scheduler and a clock that stand in for the chip in the tests of the
serving loop (``serve.run_window``, open and closed): ``max_live``
rows, each admitted request emits its first token at admission at no cost,
and every tick's batched step advances the clock by ``step_s`` and gives
each live row one token."""
from __future__ import annotations

import collections
import dataclasses
import types
from typing import Dict, List, Tuple


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(0.0, dt)


@dataclasses.dataclass
class FakeResult:
    status: str
    tokens: List[int]


class FakeScheduler:
    def __init__(self, clock: FakeClock, max_live: int, step_s: float):
        self.clock, self.max_live, self.step_s = clock, max_live, step_s
        self.queue = collections.deque()
        self.live: Dict[int, Tuple[object, int]] = {}    # rid -> (req, emitted)
        self.results: Dict[int, FakeResult] = {}
        self.events: List[Tuple[int, int, float]] = []   # (rid, index, t)
        self.submitted: List[Tuple[int, float]] = []     # (rid, t)
        self.in_flight: List[Tuple[float, int]] = []     # after each submit

    def submit(self, req) -> None:
        self.queue.append(req)
        self.submitted.append((req.request_id, self.clock()))
        self.in_flight.append((self.clock(),
                               len(self.submitted) - len(self.results)))

    def _emit(self, rid: int) -> None:
        req, n = self.live[rid]
        self.events.append((rid, n, self.clock()))
        n += 1
        if n >= req.max_new_tokens:
            del self.live[rid]
            self.results[rid] = FakeResult("completed", list(range(n)))
        else:
            self.live[rid] = (req, n)

    def step(self) -> None:
        while self.queue and len(self.live) < self.max_live:
            req = self.queue.popleft()
            self.live[req.request_id] = (req, 0)
            self._emit(req.request_id)
        if self.live:
            self.clock.t += self.step_s
            for rid in list(self.live):
                self._emit(rid)

    def stats(self) -> dict:
        return {"queued": len(self.queue)}


def fake_server(max_live: int, step_s: float, t0: float = 100.0):
    """``(server, clock)``: an object with the ``sched`` the loops drive."""
    clock = FakeClock(t0)
    return types.SimpleNamespace(
        sched=FakeScheduler(clock, max_live, step_s)), clock
