"""Traffic from a mix file and a seed.

A mix file (``chipbench/traffic/<mix>.json``) gives the arrivals, the
lead-in before the window, tables of prompt and output lengths with weights,
and ``schedule_seed``. The arrivals are open-loop at ``rate_per_s``, or
closed-loop with ``backlog``: the serving loop then keeps ``max_live +
backlog`` requests submitted and unfinished, and submits the schedule's
next request as one finishes (``serve.run_window``).

The schedule (when each request is due, and its prompt and output lengths)
is drawn from ``schedule_seed`` alone, so every run seed offers the same
work at the same moments: the count of each length is fixed by its weight
(largest remainder), and the gaps are the exponential distribution's
quantiles at ``(i + 0.5) / n``, in an order drawn once, so the arrivals
remain Poisson-like. The run seed draws the prompt tokens, uniform
over the vocabulary (and, elsewhere, the weights). A schedule that moved with
the run seed would move the work inside the window: which long answers
overlap it changed the tokens served there by up to 15% between seeds.

A closed-loop schedule is ``CLOSED_LOOP_REQUESTS`` requests in an order
drawn the same way, all due at 0: each is due when the loop submits it.
16 rows at 5 ms a step for 40 s would take some 360 of them.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    request_id: int
    due_s: float            # relative to the start of traffic (lead-in start);
                            # 0 in a closed loop, where it is due when submitted
    tokens: np.ndarray      # [prompt_len] int32
    max_new_tokens: int


def counts_for(weights, n: int) -> List[int]:
    """Largest-remainder apportionment of ``n`` items over ``weights``."""
    w = np.asarray(weights, float)
    if n < 0 or (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"bad apportionment: n={n}, weights={weights}")
    exact = w / w.sum() * n
    base = np.floor(exact).astype(int)
    rest = n - int(base.sum())
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:rest]] += 1
    return base.tolist()


def _table(rng, table: dict, n: int) -> np.ndarray:
    values = np.repeat(np.asarray(table["values"], int),
                       counts_for(table["weights"], n))
    return rng.permutation(values)


CLOSED_LOOP_REQUESTS = 4096


def closed_loop(mix: dict) -> bool:
    return "backlog" in mix


def arrivals(mix: dict, seed: int, seconds: float, vocab: int) -> List[Arrival]:
    """Arrivals over ``lead_in_s + seconds`` at ``mix["rate_per_s"]``, or
    the closed loop's ``CLOSED_LOOP_REQUESTS`` in the order they are sent."""
    sched = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    if closed_loop(mix):
        n = CLOSED_LOOP_REQUESTS
        due = np.zeros(n)
    else:
        span = float(mix["lead_in_s"]) + float(seconds)
        rate = float(mix["rate_per_s"])
        n = max(1, int(round(rate * span)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)         # unit-mean quantiles
        gaps = sched.permutation(gaps) * (span / gaps.sum())  # fill the span
        due = np.cumsum(gaps) - gaps[0]                     # first one at 0
    prompts = _table(sched, mix["prompt_len"], n)
    outputs = _table(sched, mix["output_len"], n)
    rng = np.random.default_rng(int(seed))
    return [Arrival(request_id=i, due_s=float(due[i]),
                    tokens=rng.integers(0, vocab, int(prompts[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]


def max_total_len(mix: dict) -> int:
    """Longest prompt plus longest output: what ``max_len`` must hold."""
    return max(mix["prompt_len"]["values"]) + max(mix["output_len"]["values"])
