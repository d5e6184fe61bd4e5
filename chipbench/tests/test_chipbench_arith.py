"""The benchmark's yardstick on the CPU: traffic generation, the window's
arithmetic, and the operation and byte counts."""
import json
import os

import numpy as np
import pytest

from chipbench import flops, generator, window
from chipbench.arch import dense

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
               if f.endswith(".json"))


def _mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_generator_is_deterministic_per_seed(mix):
    m = _mix(mix)
    seed = 2 ** 31 + 977
    a = generator.arrivals(m, seed, 12.0, 50304)
    b = generator.arrivals(m, seed, 12.0, 50304)
    assert [(x.due_s, x.max_new_tokens) for x in a] == \
        [(x.due_s, x.max_new_tokens) for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    # Another seed draws other prompts on the same schedule.
    c = generator.arrivals(m, seed + 1, 12.0, 50304)
    assert [(x.due_s, x.tokens.size, x.max_new_tokens) for x in a] == \
        [(x.due_s, x.tokens.size, x.max_new_tokens) for x in c]
    assert not any(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))
    # The schedule is the mix's own: another schedule seed moves it (a
    # closed loop's requests are all due when sent: their order moves).
    d = generator.arrivals(dict(m, schedule_seed=m["schedule_seed"] + 1),
                           seed, 12.0, 50304)
    if generator.closed_loop(m):
        assert [x.max_new_tokens for x in a] != [x.max_new_tokens for x in d]
    else:
        assert [x.due_s for x in a] != [x.due_s for x in d]


@pytest.mark.parametrize("mix", MIXES)
def test_generator_draws_only_table_lengths_and_same_work(mix):
    m = _mix(mix)
    runs = [generator.arrivals(m, s, 12.0, 32064) for s in (3, 2 ** 33 + 5)]
    for arr in runs:
        assert {a.tokens.size for a in arr} <= set(m["prompt_len"]["values"])
        assert {a.max_new_tokens for a in arr} <= set(m["output_len"]["values"])
        assert all(a.tokens.min() >= 0 and a.tokens.max() < 32064
                   for a in arr)
        dues = [a.due_s for a in arr]
        assert dues == sorted(dues) and dues[0] == 0.0
        assert dues[-1] <= m["lead_in_s"] + 12.0
    # Every seed offers the same lengths at the same moments, and the
    # multiset of lengths follows the table's weights.
    a, b = runs
    assert [(x.due_s, x.tokens.size, x.max_new_tokens) for x in a] == \
        [(x.due_s, x.tokens.size, x.max_new_tokens) for x in b]
    if generator.closed_loop(m):
        n = generator.CLOSED_LOOP_REQUESTS
        assert {x.due_s for x in a} == {0.0}
    else:
        n = round(m["rate_per_s"] * (m["lead_in_s"] + 12.0))
    assert len(a) == n
    table = m["output_len"]
    assert [sum(1 for x in a if x.max_new_tokens == v)
            for v in table["values"]] == \
        generator.counts_for(table["weights"], n)


def test_counts_follow_weights():
    assert generator.counts_for([0.5, 0.3, 0.2], 10) == [5, 3, 2]
    assert sum(generator.counts_for([0.3, 0.3, 0.25, 0.15], 37)) == 37


def test_window_arithmetic_on_a_synthetic_timeline():
    # Request 0 due at 0.0: tokens at 1.0, 1.5, 2.5. Request 1 due at 2.0:
    # tokens at 2.2, 2.4, 4.4 (the last after the window [1, 4)).
    events = [(0, 0, 1.0), (0, 1, 1.5), (0, 2, 2.5),
              (1, 0, 2.2), (1, 1, 2.4), (1, 2, 4.4)]
    due = {0: 0.0, 1: 2.0}
    m = window.end_to_end(events, due, 1.0, 4.0)
    assert m["tokens_per_s"] == pytest.approx(5 / 3.0)    # 4.4 is outside
    # Gaps ending inside: 0.5, 1.0 (request 0) and 0.2 (request 1).
    assert m["tpot_ms"] == pytest.approx(1e3 * 1.7 / 3)
    assert m["itl_p95_ms"] == pytest.approx(1e3 * 1.0)
    # Only request 1 is due inside the window: TTFT 0.2 s.
    assert m["ttft_p95_ms"] == pytest.approx(200.0)
    assert m["n_gaps"] == 3 and m["n_first_tokens"] == 1


def test_percentile_is_over_all_samples():
    values = list(range(1, 101))
    assert window.percentile(values, 95) == 95
    assert window.percentile(values, 50) == 50
    assert window.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 95)


def test_a_request_due_in_the_window_must_have_a_token():
    with pytest.raises(ValueError):
        window.ttfts({}, {5: 1.5}, 1.0, 2.0)


OLMO = {"hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_hidden_layers": 16, "vocab_size": 50304}


def test_flops_of_one_olmo_gemm_by_hand():
    # The MLP up-projection of 16 decode rows: [16, 2048] @ [2048, 8192].
    assert flops.gemm_flops(16, 2048, 8192) == 2 * 16 * 2048 * 8192 \
        == 536870912
    assert flops.gemm_bytes(16, 2048, 8192) == \
        2 * (16 * 2048 + 2048 * 8192 + 16 * 8192) == 33882112
    # Memory-bound at 16 rows on a v5e: 33882112 B / 819e9 B/s.
    t = flops.ideal_s(536870912, 33882112, 197e12, 819e9)
    assert t == pytest.approx(33882112 / 819e9)


def test_flops_of_the_olmo_step():
    gemms = dense.weight_gemms(OLMO, 16)
    assert len(gemms) == 7 * 16 + 1 and gemms[-1] == (16, 2048, 50304)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert dense.layer_params(OLMO) == 16 * per_layer
    assert dense.matmul_params(OLMO) == 16 * per_layer + 2048 * 50304
    assert dense.decode_token_flops(OLMO, 99) == \
        2 * dense.matmul_params(OLMO) + 4 * 16 * 2048 * 100
    n = 1024
    assert dense.prefill_flops(OLMO, n) == (
        2 * 16 * per_layer * n + 2 * 2048 * 50304
        + 4 * 16 * 2048 * n * (n + 1) // 2)
    assert dense.weight_gemms(OLMO, n, head_rows=1)[-1] == (1, 2048, 50304)
