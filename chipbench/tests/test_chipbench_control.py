"""The control on the CPU at a reduced size: the program's own int8 weight
path, the precision below the bfloat16 the configurations state, driven
through the same harness and judged by the same comparison as a sound run.

At the cells' own sizes on the chip the control's readings are in
``PERF.md`` (``chipbench/calibrate.py readings --control int8``). Here, at
width 128 and four layers, int8 weights quadruple the rms logit error of
the bfloat16 program (0.020-0.023 against 0.0055-0.0057 over four seeds),
so the tiny cell's limit (``TINY_RMS_LIMIT``) passes the one and fails the
other.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import serve
from chipbench.tests.tinycell import TINY_RMS_LIMIT, run_tiny, tiny_spec


def test_control_serves_int8_weights():
    spec = tiny_spec("olmo")
    from repro.models import build
    program = build(spec.model.program_config(spec.arch))
    params = serve.build_params(spec.model, program, spec.arch, 3,
                                quantize="int8")
    packed = params["layers"]["mlp"]["wg"]
    assert packed.packed.dtype == jnp.int8 and packed.scales is not None


def test_control_is_judged_like_a_sound_run():
    seed = 2 ** 31 + 23
    sound = run_tiny("phi3", seed=seed)
    control = run_tiny("phi3", seed=seed, quantize="int8")
    assert set(control["compared"]) == set(sound["compared"])
    assert control["attempted"] == sound["attempted"]
    for c in control["compared"].values():
        assert np.isfinite(c["value"]) and c["value"] >= 0.0
    assert control["metrics"].keys() == sound["metrics"].keys()


def test_control_is_not_correct():
    """The harness's own verdict: the sound program is correct and the
    int8 control is not, on the same seed and traffic."""
    seed = 2 ** 33 + 5
    sound = run_tiny("olmo", seed=seed, width=128, layers=4)
    control = run_tiny("olmo", seed=seed, width=128, layers=4,
                       quantize="int8")
    assert sound["correct"], sound["compared"]
    assert not control["correct"], control["compared"]
    assert control["compared"]["logit_rms_err"]["value"] > TINY_RMS_LIMIT
    assert control["compared"]["compiles_in_window"]["value"] == 0


def test_control_is_not_correct_in_a_closed_loop():
    seed = 2 ** 33 + 6
    sound = run_tiny("olmo", seed=seed, width=128, layers=4, backlog=2)
    control = run_tiny("olmo", seed=seed, width=128, layers=4, backlog=2,
                       quantize="int8")
    assert sound["correct"], sound["compared"]
    assert not control["correct"], control["compared"]
    assert control["compared"]["logit_rms_err"]["value"] > TINY_RMS_LIMIT
