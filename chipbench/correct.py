"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the request with the
most served tokens, is run once through the plain float32 reference (the
architecture module's ``token_gaps``, ``chipbench/arch/<name>.py``): each
prompt followed by its served tokens, as one causal sequence. The numbers,
over every position of the sample that produced a served token:

* ``mean_gap``: the reference's best logit minus its logit of the served
  token (0 where the served token is the reference's own greedy choice),
  averaged over the served tokens. Served tokens are greedy, so a sound
  program loses only what its bfloat16 arithmetic costs near ties: a flip
  happens where the reference's top two lie closer than the program's
  error, and costs at most that error, so the mean grows as the square of
  the error.
* ``widest_gap``: the largest such gap. A sound flip costs at most the
  program's error at that position; a token altered where it is produced
  costs the width of the whole logit distribution, however many tokens
  the sample holds, where the mean dilutes it. (At the cells' sizes the
  int8 control reads less than three times what sound runs do here; it
  fails the other numbers.)
* ``logit_rms_err``: the root mean square of the gaps between the logits
  the timed path sampled from and the reference's, at the seed's probe ids
  (``probe_ids``), over the standard deviation of the reference's logits
  there. It reads the error of every layer directly rather than through
  the ties it happens to flip, and is steady from seed to seed. (The
  largest such gap, ``logit_err``, is printed; it is not compared: in the
  prefill cell the int8 control reads 2.9 times what sound runs do.)

A configuration's ``correct`` group names the limit of each number compared
(``<number>_limit``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

PROBES = 64   # vocabulary ids whose logits are captured at every position


def probe_ids(seed: int, vocab: int, k: int = PROBES) -> np.ndarray:
    """``k`` distinct vocabulary ids drawn from the seed."""
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(vocab, size=k, replace=False)).astype(np.int32)


def sample_requests(served: Dict[int, List[int]], seed: int, n: int,
                    rows: Dict[int, int]) -> List[int]:
    """``n`` finished request ids: the one with the most served tokens
    (lowest id on a tie), then one drawn from the seed for each batch row
    (``rows``: request id -> the row its batched-step tokens came from) that
    the sample does not cover yet, in an order drawn from the seed, then
    others drawn from the seed. With ``n`` at least the batch's width, every
    row the window used is checked: a fault confined to some rows cannot
    fall outside the sample."""
    ids = sorted(served)
    if not ids:
        raise ValueError("no finished request to compare")
    longest = max(ids, key=lambda r: (len(served[r]), -r))
    rng = np.random.default_rng(int(seed) + 1)
    picked = [longest]
    open_rows = sorted({rows[r] for r in ids if r in rows} - {rows.get(longest)})
    order = rng.permutation(len(open_rows)) if open_rows else []
    for row in (open_rows[j] for j in order):
        if len(picked) >= n:
            break
        here = [r for r in ids if rows.get(r) == row]
        picked.append(here[int(rng.integers(len(here)))])
    rest = [r for r in ids if r not in picked]
    k = max(0, min(n - len(picked), len(rest)))
    drawn = rng.choice(len(rest), size=k, replace=False)
    return [longest] + sorted(picked[1:] + [rest[i] for i in drawn])


def teacher_forced(prompts: Sequence[np.ndarray], served: Sequence[List[int]],
                   length: int):
    """Padded inputs, targets and mask [N, length]: position t of sequence i
    holds its t-th input token, and where it produced a served token the
    target is that token."""
    n = len(prompts)
    tokens = np.zeros((n, length), np.int32)
    targets = np.zeros((n, length), np.int32)
    mask = np.zeros((n, length), bool)
    for i, (p, out) in enumerate(zip(prompts, served)):
        seq = np.concatenate([np.asarray(p, np.int32),
                              np.asarray(out[:-1], np.int32)])
        if seq.size > length:
            raise ValueError(f"sequence of {seq.size} tokens > {length}")
        tokens[i, :seq.size] = seq
        s = len(p)
        targets[i, s - 1:s - 1 + len(out)] = out
        mask[i, s - 1:s - 1 + len(out)] = True
    return tokens, targets, mask


def compare(model, arch: dict, seed: int, prompts, served, probed, ids,
            length: int) -> dict:
    """Run the reference of the architecture module ``model`` over the
    sample and return the numbers and the counts. ``probed[i]``:
    [len(served[i]), K] logits the program sampled request i's tokens from,
    at vocabulary ids ``ids``."""
    tokens, targets, mask = teacher_forced(prompts, served, length)
    gaps, best, ref = model.token_gaps(arch, seed, tokens, targets, ids)
    gaps, best, ref = np.asarray(gaps), np.asarray(best), np.asarray(ref)
    got = np.zeros_like(ref)
    for i, (p, rows) in enumerate(zip(prompts, probed)):
        got[i, len(p) - 1:len(p) - 1 + len(rows)] = rows
    ref_m, got_m, gaps = ref[mask], got[mask], gaps[mask]
    err = np.abs(got_m - ref_m)
    return {"widest_gap": float(gaps.max()),
            "logit_err": float(err.max() / ref_m.std()),
            "tokens": int(mask.sum()),
            "differ": int((best[mask] != targets[mask]).sum()),
            "mean_gap": float(gaps.mean()),
            "logit_rms_err": float(np.sqrt((err ** 2).mean()) / ref_m.std())}
