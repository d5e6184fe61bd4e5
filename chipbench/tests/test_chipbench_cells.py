"""``BENCHMARK.json`` and the files it names: every cell's configuration,
traffic mix and per-layer metric is a file of its own that the harness
finds by name, and the entry point refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cell, generator

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_and_fit(name):
    spec = cell.load(ROOT, name)
    serving = spec.serving
    assert generator.max_total_len(spec.traffic) <= serving["max_len"]
    assert serving["max_len"] % serving["block_size"] == 0
    assert serving["max_len"] <= spec.config["max_position_embeddings"]
    if generator.closed_loop(spec.traffic):
        assert spec.traffic["backlog"] >= 1
    else:
        assert spec.traffic["rate_per_s"] > 0
    # The check compares a request from every batch row.
    assert spec.traffic["check"]["requests"] >= serving["max_live"]
    assert set(spec.config["correct"]) == {
        "mean_gap_limit", "logit_rms_err_limit", "widest_gap_limit"}
    spec.model.program_config(spec.arch)  # the program runs this architecture
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec.per_layer
    for m in spec.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_matches_its_entry(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    mod = cell.metric_reader(metric)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == \
        (entry["unit"], entry["layer"], entry["moves"])
    assert callable(mod.read)


def test_configuration_files_are_the_benchmark_entries():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def test_entry_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _run_entry(ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())


def test_entry_in_a_directory_of_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_entry(str(tmp_path))
    assert out.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())


@pytest.mark.parametrize("name", CELLS)
def test_throughput_is_judged_only_where_the_loop_is_closed(name):
    # In an open loop below its knee the tokens in the window are the
    # schedule's (and the lead-in's backlog spilling into it), not the
    # program's speed; only a closed loop's full batch reads capacity.
    spec = cell.load(ROOT, name)
    e2e = {m["name"] for m in spec.end_to_end}
    assert ("tokens_per_s" in e2e) == generator.closed_loop(spec.traffic)


def test_offline_cell_reports_its_metrics():
    spec = cell.load(ROOT, "olmo1b-offline")
    assert (spec.config_name, spec.traffic_name, spec.chips) == \
        ("olmo-1b.live16-len1024", "backlog-olmo1b", 1)
    assert {m["name"] for m in spec.end_to_end} == \
        {"tokens_per_s", "tpot_ms", "itl_p95_ms", "setup_s"}
    assert {m["name"] for m in spec.per_layer} == \
        {"host_ms_per_tick", "device_idle_share", "mfu.decode",
         "gemm_roofline.decode", "kv_view_ms.decode"}
    # The lengths are the decode cell's: only the arrivals differ.
    decode = cell.load(ROOT, "olmo1b-decode").traffic
    for key in ("prompt_len", "output_len", "schedule_seed", "check"):
        assert spec.traffic[key] == decode[key]
