"""The harness, past its look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault a served cell
can have, and true for the sound program (CPU, reduced size)."""
import pytest

from chipbench import correct
from chipbench.faults import FAULTS
from chipbench.generator import CLOSED_LOOP_REQUESTS
from chipbench.tests.tinycell import TINY_LIMIT, TINY_WIDEST_LIMIT, run_tiny


@pytest.mark.parametrize("model_type", ["olmo", "phi3"])
def test_sound_program_is_correct(model_type):
    out = run_tiny(model_type)
    gap = out["compared"]["mean_gap"]
    assert out["correct"], out["compared"]
    assert gap["limit"] == TINY_LIMIT and gap["value"] < TINY_LIMIT / 2
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["compiles_in_window"]["value"] == 0
    assert out["compared"]["widest_gap"]["value"] < TINY_WIDEST_LIMIT / 2
    # The open-loop cells judge no throughput (it reads how much of the
    # lead-in's backlog spills into the window, not the program's speed).
    assert set(out["metrics"]) == {"tpot_ms", "itl_p95_ms", "ttft_p95_ms",
                                   "setup_s"}
    assert list(out)[-1] == "compared"


def test_sound_program_is_correct_in_a_closed_loop():
    stats = {}
    out = run_tiny("olmo", backlog=2, stats=stats)
    assert out["correct"], out["compared"]
    assert out["compared"]["mean_gap"]["value"] < TINY_LIMIT / 2
    assert out["compared"]["compiles_in_window"]["value"] == 0
    # Only the requests the loop submitted are attempted, and all finish.
    assert out["failed"] == 0
    assert 6 <= out["attempted"] < CLOSED_LOOP_REQUESTS
    assert set(out["metrics"]) == {"tokens_per_s", "tpot_ms", "itl_p95_ms",
                                   "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert stats["n_first_tokens"] >= 1
    assert stats["sample_rows"] == {0, 1, 2, 3}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct_in_a_closed_loop(fault, monkeypatch):
    FAULTS[fault](monkeypatch.setattr)
    out = run_tiny("olmo", backlog=2)
    assert not out["correct"], out["compared"]
    assert out["compared"]["mean_gap"]["value"] > 2 * TINY_LIMIT


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch.setattr)
    out = run_tiny("olmo")
    assert not out["correct"], out["compared"]
    # Each fault fails the mean gap, the number an altered token shows in.
    assert out["compared"]["mean_gap"]["value"] > 2 * TINY_LIMIT


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct_in_a_sample_as_wide_as_the_batch(
        fault, monkeypatch):
    # As in the decode and offline cells: the check compares as many
    # requests as the batch has rows, one from each row, so a fault
    # confined to some rows cannot fall outside it.
    FAULTS[fault](monkeypatch.setattr)
    stats = {}
    out = run_tiny("olmo", backlog=2, requests=4, stats=stats)
    assert stats["sample_rows"] == {0, 1, 2, 3}
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("backlog", [0, 2])
def test_altered_token_fails_the_widest_gap(backlog, monkeypatch):
    # One altered token a request costs the whole width of the logits: the
    # widest gap sees it however many tokens the sample holds, where the
    # mean over them dilutes it.
    FAULTS["token_altered"](monkeypatch.setattr)
    out = run_tiny("olmo", backlog=backlog, requests=4)
    assert out["compared"]["widest_gap"]["value"] > 2 * TINY_WIDEST_LIMIT
    assert not out["correct"]


def _longest(served):
    return max(served, key=lambda r: (len(served[r]), -r))


@pytest.mark.parametrize("n,width", [(16, 16), (16, 8), (3, 16)])
def test_sample_covers_the_batch_rows(n, width):
    served = {r: [0] * (5 + r % 7) for r in range(40)}
    rows = {r: (r * 7) % width for r in served}
    for seed in range(2 ** 31, 2 ** 31 + 20):
        got = correct.sample_requests(served, seed, n, rows)
        assert len(got) == len(set(got)) == n
        assert got[0] == _longest(served)
        assert len({rows[r] for r in got}) == min(n, width)


def test_sample_without_rows_is_the_seeded_draw():
    served = {r: [0] * (5 + r % 7) for r in range(40)}
    a = correct.sample_requests(served, 2 ** 31 + 5, 16, {})
    assert a == correct.sample_requests(served, 2 ** 31 + 5, 16, {})
    assert len(set(a)) == 16 and a[0] == _longest(served)
    assert a != correct.sample_requests(served, 2 ** 31 + 6, 16, {})
