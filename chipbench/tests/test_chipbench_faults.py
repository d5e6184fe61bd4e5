"""The harness, past its look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault a served cell
can have, and true for the sound program (CPU, reduced size)."""
import pytest

from chipbench.faults import FAULTS
from chipbench.tests.tinycell import TINY_LIMIT, run_tiny


@pytest.mark.parametrize("model_type", ["olmo", "phi3"])
def test_sound_program_is_correct(model_type):
    out = run_tiny(model_type)
    gap = out["compared"]["mean_gap"]
    assert out["correct"], out["compared"]
    assert gap["limit"] == TINY_LIMIT and gap["value"] < TINY_LIMIT / 2
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["compiles_in_window"]["value"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "tpot_ms", "itl_p95_ms",
                                   "ttft_p95_ms", "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch.setattr)
    out = run_tiny("olmo")
    assert not out["correct"], out["compared"]
    # Each fault fails the mean gap, the number an altered token shows in.
    assert out["compared"]["mean_gap"]["value"] > 2 * TINY_LIMIT
