"""The measured window and its arithmetic.

A run's timeline is a list of token events ``(request_id, index, t)`` (the
index-th token of that request became ready on the host at ``t``) and each
request's due time. All times share one clock (``time.perf_counter``). The
window is ``[start, end)``.

* ``tokens_per_s``: token events inside the window over its length.
* ``tpot_ms``: the mean gap between consecutive tokens of a request, over
  every gap that ends inside the window, of every request.
* ``itl_p95_ms``: the 95th percentile of those same gaps.
* ``ttft_p95_ms``: the 95th percentile, over every request due inside the
  window, of due time to first token.

Percentiles are the nearest-rank ones over all samples: no median of chunks.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100])."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])


def timelines(events: Sequence[Tuple[int, int, float]]) -> Dict[int, List[float]]:
    """request_id -> token ready times in index order."""
    out: Dict[int, Dict[int, float]] = {}
    for rid, idx, t in events:
        out.setdefault(rid, {})[idx] = t
    return {rid: [d[i] for i in sorted(d)] for rid, d in out.items()}


def gaps_in(lines: Dict[int, List[float]], start: float, end: float) -> List[float]:
    return [b - a for ts in lines.values() for a, b in zip(ts, ts[1:])
            if start <= b < end]


def tokens_in(lines: Dict[int, List[float]], start: float, end: float) -> int:
    return sum(1 for ts in lines.values() for t in ts if start <= t < end)


def ttfts(lines: Dict[int, List[float]], due: Dict[int, float],
          start: float, end: float) -> List[float]:
    """Due time to first token for each request due inside the window. A
    request due in the window with no token is an error: the run drains."""
    out = []
    for rid, d in due.items():
        if start <= d < end:
            if not lines.get(rid):
                raise ValueError(f"request {rid} due in the window has no token")
            out.append(lines[rid][0] - d)
    return out


def end_to_end(events, due: Dict[int, float], start: float,
               end: float) -> Dict[str, float]:
    lines = timelines(events)
    gaps = gaps_in(lines, start, end)
    first = ttfts(lines, due, start, end)
    return {
        "tokens_per_s": tokens_in(lines, start, end) / (end - start),
        "tpot_ms": 1e3 * math.fsum(gaps) / len(gaps),
        "itl_p95_ms": 1e3 * percentile(gaps, 95),
        "ttft_p95_ms": 1e3 * percentile(first, 95),
        "ttft_p50_ms": 1e3 * percentile(first, 50),
        # Where the two tails sit among their neighbours (printed only).
        "itl_p90_p93_p97_ms": [1e3 * percentile(gaps, q) for q in (90, 93, 97)],
        "ttft_p90_p93_p97_ms": [1e3 * percentile(first, q)
                                for q in (90, 93, 97)],
        "n_gaps": len(gaps),
        "n_first_tokens": len(first),
    }
