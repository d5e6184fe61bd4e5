"""Architectures as modules (``chipbench/arch/``): the dense module gives the
counts, leaves and reference logits the harness gave before it held them,
and a new architecture is files and entries only."""
import gzip
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import cell, flops, program as P, trace as T
from chipbench import weights as W
from chipbench.arch import dense
from chipbench.peaks import peaks
from chipbench.tests.tinycell import cut, run_spec, tiny_spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(HERE, "tests", "data")

# Computed by the harness before the dense code moved into its module.
COUNTS = {
    "olmo-1b.live16-len1024": {
        "decode_token_flops": (2353659904, 2487746560),
        "prefill_flops": (2268015886336, 4365092388864),
        "step_gemm_ideal_s": 0.002905154266178266,
        "kv_elements": (537395200, 536870912)},
    "olmo-1b.live8-len2048": {
        "decode_token_flops": (2353659904, 2487746560),
        "prefill_flops": (2268015886336, 4365092388864),
        "step_gemm_ideal_s": 0.002905154266178266,
        "kv_elements": (537395200, 536870912)},
    "phi3-mini.live4-len1024": {
        "decode_token_flops": (7445151744, 7847411712),
        "prefill_flops": (7628260245504, 14641044258816),
        "step_gemm_ideal_s": 0.009164409904761904,
        "kv_elements": (404226048, 402653184)},
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_dense_counts_are_the_harness_counts(name):
    config, model = cell.read_config(ROOT, name)
    assert model.__file__ == os.path.join(HERE, "arch", "dense.py")
    arch, pins = model.arch_of(config), COUNTS[name]
    assert tuple(model.decode_token_flops(arch, p) for p in (0, 1023)) == \
        pins["decode_token_flops"]
    assert tuple(model.prefill_flops(arch, n) for n in (1024, 1920)) == \
        pins["prefill_flops"]
    assert flops.step_gemm_ideal_s(model.weight_gemms(arch, 16), 197e12,
                                   819e9) == pins["step_gemm_ideal_s"]
    assert tuple(model.kv_elements(arch, config["serving"])) == \
        pins["kv_elements"]


SEED = 2 ** 32 + 17
# sha256 of each leaf's bfloat16 bits, and its first four as uint16, drawn
# at tinycell's sizes before the draws moved.
LEAVES = {
    "embed": ("bf56ab0ea922a93c4cb5d87a8f9f9ee7"
              "4961d15d3f2e9b22bade7c9cea338177",
              [15620, 48009, 48401, 48270]),
    "w_down": ("c50f2cbdfa3940e6e585c93cd4ff3768"
               "c1ff9e83c92b153311bbf5e6248d3e73",
               [48477, 15518, 47938, 15572]),
    "norm1": ("8bb4680dead72677c7b329b4df8009c1"
              "ccda35603d54031359b428d9fc16faf6",
              [16267, 16284, 16277, 16247]),
}


def test_dense_leaves_are_the_harness_draws():
    arch = tiny_spec("olmo").arch
    key, d = W.root_key(SEED), arch["hidden_size"]
    got = {"embed": dense.leaf(key, "embed", (arch["vocab_size"], d)),
           "w_down": dense.leaf(key, "w_down",
                                dense.layer_shapes(arch)["w_down"], 1),
           "norm1": dense.leaf(key, "norm1", (d,), 1)}
    for name, (digest, first) in LEAVES.items():
        bits = np.asarray(got[name]).view(np.uint16)
        assert hashlib.sha256(bits.tobytes()).hexdigest() == digest, name
        assert bits.ravel()[:4].tolist() == first, name


# Reference logits at tinycell's sizes before the reference moved: four
# (sequence, position, id) picks, the sum and the largest magnitude. The
# float32 sums' order follows the CPU's threads, which moves a logit by
# some 3e-8 (2e-7 of the largest); a changed term moves it by far more.
LOGITS = {
    "olmo": ([-0.20743755996227264, -0.10575243085622787,
              -0.12226466089487076, -0.04205527901649475],
             -83.21921003413172, 1.2755483388900757),
    "phi3": ([0.18222400546073914, 0.2493489384651184,
              -0.21155185997486115, 0.16891814768314362],
             -90.82054553428588, 0.7328441143035889),
}
PICKS = ((0, 0, 0), (0, 11, 17), (1, 5, 4095), (1, 11, 8191))


@pytest.mark.parametrize("model_type", sorted(LOGITS))
def test_dense_reference_logits_are_the_harness_logits(model_type):
    arch = tiny_spec(model_type).arch
    tokens = np.random.default_rng(0).integers(
        0, arch["vocab_size"], (2, 12)).astype(np.int32)
    logits = np.asarray(dense.logits(arch, SEED, tokens))
    picks, total, top = LOGITS[model_type]
    assert [float(logits[p]) for p in PICKS] == pytest.approx(picks, abs=1e-6)
    assert float(logits.astype(np.float64).sum()) == pytest.approx(total,
                                                                 abs=1e-3)
    assert float(np.abs(logits).max()) == pytest.approx(top, abs=1e-6)


# ---------------------------------------------------------------------------
# A new architecture: a module, a configuration and entries, nothing else
# ---------------------------------------------------------------------------

TOY = '''"""A second architecture: the dense one, recording every call."""
from chipbench.arch import dense

CALLS = []
PROVIDED = ("arch_of", "program_config", "param_tree", "token_gaps",
            "weight_gemms", "weight_shapes", "decode_token_flops",
            "prefill_flops", "kv_elements")


def _recorded(name):
    fn = getattr(dense, name)

    def call(*args, **kw):
        CALLS.append(name)
        return fn(*args, **kw)
    return call


for _name in PROVIDED:
    globals()[_name] = _recorded(_name)
'''


def _files(directory):
    out = {}
    for base, dirs, names in os.walk(directory):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in names:
            path = os.path.join(base, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, directory)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def _copy_with(tmp_path, arch, base="olmo-1b.live8-len2048",
               traffic="prefill-heavy-olmo1b"):
    """The benchmark copied under ``tmp_path`` with one more configuration
    (``base``'s, naming the architecture ``arch``, or none where it is
    None) and a cell of it: its files and entries only."""
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(HERE, "configs", base + ".json")) as f:
        config = json.load(f)
    config["name"] = "toy.live8-len2048"
    if arch is None:
        del config["arch"]
    else:
        config["arch"] = arch
    path = tmp_path / "chipbench" / "configs" / (config["name"] + ".json")
    path.write_text(json.dumps(config, indent=1))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": config["name"], "source": config["source"],
        "file": "chipbench/configs/" + config["name"] + ".json",
        "reduced": [], "why": "a second architecture module"})
    bench["workloads"].append({
        "name": "toy-prefill", "config": config["name"], "traffic": traffic,
        "chips": 1, "why": "a second architecture module"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return path


def test_an_architecture_is_a_module_and_a_configuration(tmp_path):
    root = tmp_path
    config_path = _copy_with(tmp_path, "toy_dense")
    toy = root / "chipbench" / "arch" / "toy_dense.py"
    toy.write_text(TOY)
    spec = cell.load(str(root), "toy-prefill")
    assert spec.model.__file__ == str(toy)
    # Served through the harness at tinycell's sizes, judged by its own
    # reference.
    spec.model.CALLS.clear()
    out = run_spec(cut(spec), 2 ** 31 + 101)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"arch_of", "program_config", "param_tree",
            "token_gaps"} <= set(spec.model.CALLS)
    # The per-layer readers take its counts: on two ticks of olmo1b-prefill
    # recorded on a TPU v5e they read what they read through ``dense``.
    spec.model.CALLS.clear()
    ctx = T.load_context(os.path.join(DATA, "olmo1b-prefill.trace.json.gz"),
                         spec.model, spec.arch, spec.serving,
                         peaks("TPU v5 lite"))
    read = {n: cell.metric_reader(n).read(ctx)
            for n in ("mfu.decode", "mfu.prefill", "gemm_roofline.decode",
                      "gemm_roofline.prefill")}
    assert read == pytest.approx({
        "mfu.decode": 0.10348758191635013, "mfu.prefill": 58.887586338751994,
        "gemm_roofline.decode": 72.74267824836296,
        "gemm_roofline.prefill": 77.9251306313882}, rel=1e-12)
    spans = T.load_context(
        os.path.join(DATA, "olmo1b-prefill.spans.trace.json.gz"), spec.model,
        spec.arch, spec.serving, peaks("TPU v5 lite"))
    with gzip.open(os.path.join(DATA, "olmo1b-prefill.spans.program.json.gz"),
                   "rt") as f:
        scopes = json.load(f)["op_scopes"]
    assert P.step_scope_ms(spans, scopes)["kv_view"] == \
        pytest.approx(32.833122, rel=1e-9)
    assert {"weight_shapes", "weight_gemms", "decode_token_flops",
            "prefill_flops", "kv_elements"} <= set(spec.model.CALLS)
    # Nothing of the copy but the new files differs from the repo's.
    repo = _files(HERE)
    changed = {k for k, v in _files(root / "chipbench").items()
               if repo.get(k) != v}
    assert changed == {os.path.join("arch", "toy_dense.py"),
                       os.path.relpath(config_path, root / "chipbench")}


@pytest.mark.parametrize("arch", [None, "absent"])
def test_a_configuration_naming_no_module_stops_before_the_device(tmp_path,
                                                                  arch):
    path = _copy_with(tmp_path, arch)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, os.path.join(tmp_path, "chipbench", "run.py"),
         "--workload", "toy-prefill", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=240)
    assert out.returncode != 0
    assert str(path) in out.stderr
    assert "no TPU found" not in out.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())
    with pytest.raises(SystemExit, match="toy.live8-len2048.json"):
        cell.load(str(tmp_path), "toy-prefill")
