"""A cell at a size the CPU runs in seconds, for the harness's tests: the
real configuration files with every size cut, and a short mix."""
import dataclasses
import json
import os

from chipbench import cell

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CONFIGS = {"olmo": "olmo-1b.live16-len1024", "phi3": "phi3-mini.live4-len1024"}
# Sound tiny runs read a mean gap of at most 0.00004 and an rms logit
# error of at most 0.0057 (olmo and phi3 shapes, four seeds each); int8
# weights at width 128 and four layers read an rms error of 0.0204 or more,
# and each planted fault a mean gap of 0.0099 or more. (Tiny runs flip too
# few tokens for the mean gap to tell int8 from bfloat16.) The widest gap
# reads at most 0.0032 sound, and 1.13-1.43 with an altered token or half
# the batch left out (olmo shapes, both loops, three seeds each).
TINY_LIMIT = 0.002
TINY_RMS_LIMIT = 0.011
TINY_WIDEST_LIMIT = 0.1


def tiny_spec(model_type: str = "olmo", width: int = 64,
              layers: int = 2, backlog: int = 0,
              requests: int = 100) -> cell.CellSpec:
    """``width`` is the hidden size (heads of 16 lanes), ``layers`` the
    depth; the feed-forward width is twice the hidden size. ``backlog``
    makes the mix a closed loop with ``max_live + backlog`` requests
    outstanding, measured with the metrics of ``olmo1b-offline``; without
    it the mix is open-loop, with those of ``olmo1b-prefill``. The check
    compares ``requests`` of the finished requests."""
    config, model = cell.read_config(ROOT, CONFIGS[model_type])
    return cut(cell.CellSpec(name="tiny", chips=1, config_name="tiny",
                             traffic_name="tiny", config=config, traffic={},
                             end_to_end=[], per_layer=[], model=model),
               width, layers, backlog, requests)


def cut(spec: cell.CellSpec, width: int = 64, layers: int = 2,
        backlog: int = 0, requests: int = 100) -> cell.CellSpec:
    """``spec`` at ``tiny_spec``'s sizes, mix and metrics (its name and
    architecture module kept)."""
    config = json.loads(json.dumps(spec.config))
    config.update(hidden_size=width, intermediate_size=2 * width,
                  num_hidden_layers=layers, num_attention_heads=width // 16,
                  num_key_value_heads=width // 16, vocab_size=8192)
    config["serving"] = dict(config["serving"], max_live=4, max_len=64)
    config["correct"] = {"mean_gap_limit": TINY_LIMIT,
                         "logit_rms_err_limit": TINY_RMS_LIMIT,
                         "widest_gap_limit": TINY_WIDEST_LIMIT}
    mix = {"rate_per_s": 20.0, "lead_in_s": 0.5,
           "prompt_len": {"values": [4, 8], "weights": [0.5, 0.5]},
           "output_len": {"values": [16, 40], "weights": [0.5, 0.5]},
           "check": {"requests": requests}}
    if backlog:
        del mix["rate_per_s"]
        mix["backlog"] = backlog
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e, layers = cell.metrics_for(
        bench, "olmo1b-offline" if backlog else "olmo1b-prefill")
    return dataclasses.replace(spec, traffic_name="tiny", config=config,
                               traffic=mix, end_to_end=e2e, per_layer=layers)


def run_tiny(model_type: str = "olmo", seed: int = 2 ** 31 + 11,
             width: int = 64, layers: int = 2, backlog: int = 0,
             requests: int = 100, **kw) -> dict:
    return run_spec(tiny_spec(model_type, width, layers, backlog, requests),
                    seed, **kw)


def run_spec(spec: cell.CellSpec, seed: int, **kw) -> dict:
    """One run of a cut cell on the CPU, with a window of one second."""
    import time
    import jax
    return cell.run(spec, seed=seed, seconds=1.0, trace=False,
                    device=jax.devices()[0],
                    process_start=time.perf_counter(), log=lambda m: None,
                    trace_dir=os.path.join(ROOT, ".chipbench", "test-trace"),
                    **kw)
