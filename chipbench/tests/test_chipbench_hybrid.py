"""The Mamba-2 hybrid architecture (``chipbench/arch/mamba2_hybrid.py``) and
its cell, ``granite-h-micro-offline``: the counts against hand arithmetic
at the published sizes; the program served through the continuous
scheduler against the plain float32 reference at a small size on the CPU
(five layers ``[m, m, a, m, m]``); the comparison that decides ``correct``
passing the sound program and failing the int8 control and each planted
fault; and the two readers of the recurrent update on a hand-built
trace."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell, flops, serve, trace as T
from chipbench import weights as W
from chipbench.arch import mamba2_hybrid as M
from chipbench.faults import FAULTS
from chipbench.tests.test_chipbench_program import (HOST_SPANS, MS, PEAKS,
                                                    _gemm, _xspace)
from chipbench.tests.tinycell import (TINY_LIMIT, TINY_RMS_LIMIT,
                                      TINY_WIDEST_LIMIT, run_spec)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "granite-h-micro-offline"
CONFIG = "granite-4.0-h-micro.live32-len1024"
SMALL = ["mamba", "mamba", "attention", "mamba", "mamba"]
SEED = 2 ** 32 + 17


def full_arch():
    config, model = cell.read_config(ROOT, CONFIG)
    assert model.__file__ == os.path.join(HERE, "arch", "mamba2_hybrid.py")
    return model.arch_of(config), config


def small_spec(width: int = 64, requests: int = 100) -> cell.CellSpec:
    """The cell with every size cut: ``width`` the hidden size (attention
    heads of 16, half as many KV heads; Mamba heads of 16 with state 16 and
    chunk 8), five layers ``SMALL``, 4 rows over 64 positions, and a closed
    loop of short requests."""
    spec = cell.load(ROOT, CELL)
    config = json.loads(json.dumps(spec.config))
    config.update(hidden_size=width, shared_intermediate_size=2 * width,
                  intermediate_size=2 * width, num_hidden_layers=5,
                  layer_types=SMALL, num_attention_heads=width // 16,
                  num_key_value_heads=width // 32, vocab_size=8192,
                  mamba_n_heads=width // 16, mamba_d_head=16,
                  mamba_expand=1, mamba_d_state=16, mamba_chunk_size=8)
    config["serving"] = dict(config["serving"], max_live=4, max_len=64)
    config["correct"] = {"mean_gap_limit": TINY_LIMIT,
                         "logit_rms_err_limit": TINY_RMS_LIMIT,
                         "widest_gap_limit": TINY_WIDEST_LIMIT}
    mix = {"backlog": 2, "lead_in_s": 0.5,
           "prompt_len": {"values": [4, 8], "weights": [0.5, 0.5]},
           "output_len": {"values": [16, 40], "weights": [0.5, 0.5]},
           "check": {"requests": requests}}
    return dataclasses.replace(spec, config=config, traffic=mix)


# ----- the cell and its counts ----------------------------------------------


def test_cell_files_hold_the_published_model():
    spec = cell.load(ROOT, CELL)
    assert (spec.config_name, spec.traffic_name, spec.chips) == \
        (CONFIG, "backlog-granite-h-micro", 1)
    assert spec.config["reduced"] == []
    assert [i for i, t in enumerate(spec.config["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert {m["name"] for m in spec.end_to_end} == \
        {"tokens_per_s", "tpot_ms", "itl_p95_ms", "setup_s"}
    assert {m["name"] for m in spec.per_layer} == {
        "host_ms_per_tick", "device_idle_share", "mfu.decode",
        "gemm_roofline.decode", "kv_view_ms.decode", "ssm_state_ms.decode",
        "ssm_roofline.decode"}
    assert spec.traffic["check"]["requests"] == spec.serving["max_live"] == 32
    assert spec.traffic["backlog"] == 8
    cfg = spec.model.program_config(spec.arch)
    assert cfg.num_params() == 3191394048


def test_counts_by_hand():
    arch, config = full_arch()
    gemms = M.weight_gemms(arch, 32)
    # 36 mamba layers (in_proj, out_proj, 3 MLP), 4 attention layers (4
    # projections, 3 MLP), the head.
    assert len(gemms) == 36 * 5 + 4 * 7 + 1
    assert gemms[0] == (32, 2048, 8512) and gemms[1] == (32, 4096, 2048)
    assert gemms[5 * 5:5 * 5 + 4] == [(32, 2048, 2048), (32, 2048, 512),
                                      (32, 2048, 512), (32, 2048, 2048)]
    assert gemms[-1] == (32, 2048, 100352)
    assert M.weight_gemms(arch, 64, head_rows=1)[-1] == (1, 2048, 100352)
    weights = (36 * (2048 * 8512 + 4096 * 2048)
               + 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
               + 40 * 3 * 2048 * 8192)
    assert weights == 2984771584
    assert sum(k * n for _, k, n in gemms) == weights + 2048 * 100352
    assert set(M.weight_shapes(arch)) == {
        (2048, 8512), (4096, 2048), (2048, 2048), (2048, 512), (2048, 8192),
        (8192, 2048), (2048, 100352)}
    # One token's update: 6 operations a state element (64 x 64 x 128) and
    # 2 a conv tap and channel (4 x 4352), in 36 layers.
    ssm = 36 * (6 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert ssm == 114499584
    head = 2048 * 100352
    assert M.decode_token_flops(arch, 0) == 2 * (weights + head) + \
        4 * 4 * 2048 * 1 + ssm
    assert M.decode_token_flops(arch, 1023) == 2 * (weights + head) + \
        4 * 4 * 2048 * 1024 + ssm
    # A 256-token prompt is one chunk of 256: 256 x 257 / 2 causal pairs at
    # 2 x 128 + 2 x 4096 each, 4 x 64 x 64 x 128 + 2 x 4 x 4352 a token.
    ssd = 36 * (256 * 257 // 2 * (2 * 128 + 2 * 4096)
                + 256 * (4 * 64 * 64 * 128 + 2 * 4 * 4352))
    assert M.prefill_flops(arch, 256) == (2 * weights * 256 + 2 * head
                                          + 4 * 4 * 2048 * 256 * 257 // 2
                                          + ssd)
    # 300 tokens: a chunk of 256 and one of 44.
    pairs = 256 * 257 // 2 + 44 * 45 // 2
    assert M.ssd_flops(arch, 300) == 36 * (
        pairs * (2 * 128 + 2 * 4096) + 300 * (4 * 64 * 64 * 128
                                             + 2 * 4 * 4352))
    # KV over the 4 attention layers: 2048 blocks + the null block of 16
    # positions of 8 heads of 64; the view 32 rows of 1024.
    assert M.kv_elements(arch, config["serving"]) == (
        2049 * 16 * 4 * 8 * 64, 32 * 1024 * 4 * 8 * 64)
    # 77.4 MB of float32 state a row (36 x (64 x 64 x 128 + 3 x 4352)),
    # read and written: 4.95 GB for 32 rows.
    assert 36 * (64 * 64 * 128 + 3 * 4352) * 4 == 77377536
    assert M.ssm_state_bytes(arch, 32) == 2 * 32 * 77377536 == 4952162304
    assert M.ssm_state_flops(arch, 32) == 32 * ssm
    # The store's leaves: [36, 32, 64, 64, 128] and [36, 32, 3, 4352].
    assert M.ssm_store_elements(arch, config["serving"]) == (
        36 * 32 * 64 * 64 * 128, 36 * 32 * 3 * 4352)


def test_layer_draws_equal_the_stacked_draw():
    arch = small_spec().arch
    key = W.root_key(SEED)
    tree = jax.jit(lambda k: M.param_tree(k, arch))(key)
    shapes = M.layer_shapes(arch)
    for j, layer in enumerate(M.layers_of(arch, M.MAMBA)):
        for name, got in (("in_proj", tree["mamba_layers"]["ssm"]["in_proj"]),
                          ("dt_bias", tree["mamba_layers"]["ssm"]["dt_bias"]),
                          ("norm2", tree["mamba_layers"]["norm2"]["scale"])):
            one = M.leaf(key, name, shapes[name], layer)
            assert np.array_equal(np.asarray(got[j]),
                                  np.asarray(one.astype(got.dtype)))
    wq = tree["attn_layers"]["attn"]["wq"]
    assert wq.shape[0] == 1 and np.array_equal(
        np.asarray(wq[0]), np.asarray(M.leaf(key, "wq", shapes["wq"], 2)))
    # At the published sizes some heads keep a long memory (softplus of
    # dt_bias near 0.001, A near 1: a decay near 1 a token, before the
    # input's own dt) and others forget in a few tokens.
    arch = full_arch()[0]
    mamba = M.layers_of(arch, M.MAMBA)
    dt = jax.nn.softplus(M.stacked(key, "dt_bias", (64,), mamba).astype(
        jnp.float32))
    a = jnp.exp(M.stacked(key, "A_log", (64,), mamba).astype(jnp.float32))
    decay = np.asarray(jnp.exp(-dt * a))
    assert decay.max() > 0.998 and decay.min() < 0.4


# ----- the program against the reference ------------------------------------


def test_prefill_and_decode_agree_with_the_reference_in_float32():
    """Prompts prefilled, then decoded through the paged pool and the
    recurrent store by the continuous scheduler's batched step, with the
    program in float32: every served position's logits, against the
    reference's full forward over the prompt and the served tokens.
    Tolerance: 1e-4 of the largest logit; the two differ only in the order
    of float32 sums (the chunked scan against the token-by-token
    recurrence, blocked against plain matmuls), some 1e-6 of it."""
    from repro.models import build
    from repro.serve import (ContinuousConfig, ContinuousScheduler, Engine,
                             Request, ServeConfig)
    spec = small_spec()
    arch = spec.arch
    program = build(dataclasses.replace(M.program_config(arch),
                                        compute_dtype="float32"))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          M.param_tree(W.root_key(SEED), arch))
    engine = Engine(program, params, ServeConfig(max_len=64))
    ids = np.arange(arch["vocab_size"], dtype=np.int32)
    clock = serve.TokenClock(engine, ids)
    sched = ContinuousScheduler(engine, ContinuousConfig(
        queue_capacity=8, max_live=3, block_size=8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, arch["vocab_size"], n).astype(np.int32)
               for n in (5, 12, 9, 17)]
    for i, p in enumerate(prompts):
        sched.submit(Request(request_id=i, tokens=p, max_new_tokens=10))
    sched.drain()
    for i, p in enumerate(prompts):
        served = sched.results[i].tokens
        got = clock.probed_logits(i, len(served))          # [10, V]
        seq = np.concatenate([p, served[:-1]])[None]
        want = np.asarray(M.logits(arch, SEED, seq))[0, len(p) - 1:]
        scale = float(np.abs(want).max())
        assert float(np.abs(got - want).max()) <= 1e-4 * scale
        assert np.array_equal(np.argmax(want, -1), served)


def _closed(monkeypatch=None, fault=None, **kw):
    if fault is not None:
        fault(monkeypatch.setattr)
    return run_spec(small_spec(), 2 ** 31 + 11, **kw)


def test_sound_program_is_correct():
    stats = {}
    out = run_spec(small_spec(), 2 ** 31 + 11, stats=stats)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["mean_gap"]["value"] < TINY_LIMIT / 2
    assert out["compared"]["logit_rms_err"]["value"] < TINY_RMS_LIMIT * 0.6
    assert out["compared"]["compiles_in_window"]["value"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "tpot_ms", "itl_p95_ms",
                                   "setup_s"}
    assert stats["sample_rows"] == {0, 1, 2, 3}


def test_control_is_not_correct():
    """int8 weights, the precision below the served bfloat16: at this size
    they double the rms logit error (0.0120-0.0134 against 0.0057-0.0062
    sound, widths 64 and 128, two seeds each)."""
    out = run_spec(small_spec(), 2 ** 31 + 11, quantize="int8")
    assert not out["correct"], out["compared"]
    assert out["compared"]["logit_rms_err"]["value"] > TINY_RMS_LIMIT


def hybrid_state_unchanged(patch):
    """The hybrid's batched step returns the KV pool and the recurrent state
    it was given."""
    from repro.serve.scheduler import ContinuousScheduler
    build = ContinuousScheduler._build_step

    def broken(self):
        step = build(self)

        def run(params, pool_k, pool_v, state, conv, *rest):
            logits = step(params, pool_k, pool_v, state, conv, *rest)[0]
            return logits, pool_k, pool_v, state, conv
        return run
    patch(ContinuousScheduler, "_build_step", broken)


def hybrid_half_batch(patch):
    """The hybrid's batched step computes only the first half of its
    rows."""
    from repro.serve.scheduler import ContinuousScheduler
    build = ContinuousScheduler._build_step

    def broken(self):
        step, half = build(self), self.cfg.max_live // 2

        def run(*args):
            logits, *rest = step(*args)
            return (logits.at[half:].set(0.0), *rest)
        return run
    patch(ContinuousScheduler, "_build_step", broken)


# The number each fault fails by twice its limit. At this size the
# embedding (x12) outweighs what five layers add to the residual (x0.22
# each), so the greedy token mostly follows the current token: a stale
# state or a zeroed half of the batch moves the logits (rms error 0.09 and
# 0.68) more than the argmax, where an altered token moves the served token
# itself.
@pytest.mark.parametrize("fault,number", [
    (FAULTS["token_altered"], "mean_gap"),
    (hybrid_state_unchanged, "logit_rms_err"),
    (hybrid_half_batch, "logit_rms_err")],
    ids=["token_altered", "state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(fault, number, monkeypatch):
    stats = {}
    out = _closed(monkeypatch, fault, stats=stats)
    assert stats["sample_rows"] == {0, 1, 2, 3}
    assert not out["correct"], out["compared"]
    got = out["compared"][number]
    assert got["value"] > 2 * got["limit"], out["compared"]


# ----- the readers of the recurrent update ----------------------------------

SERVING = {"max_live": 2, "max_len": 4, "block_size": 2}
# One batched step [30, 40) ms: the layer loop (left out: it holds the
# rest), in it a state update (scope ssm_state, 1 ms), its write into the
# scan's stacked output and the conv window's (no named scope, each a whole
# leaf of the store: 2 ms and 1 ms), an op of no scope (it takes the loop's:
# none), the attention layer's cond holding an op of no scope, a GEMM, and
# an op of the store's size under another scope.
STEP_OPS = [
    (30, 39, "%while.5 = (s32[]) while(s32[] %t)", "jit(step)/while"),
    (30, 31, "%fusion.8 = f32[2,64,64]{} fusion()",
     "jit(step)/while/body/closed_call/ssm_state/mul"),
    (31, 33, "%fusion.9 = f32[36,2,64,64,128]{} fusion()",
     "jit(step)/while/body/dynamic_update_slice"),
    (33, 34, "%fusion.10 = f32[36,2,3,4352]{} fusion()",
     "jit(step)/while/body/dynamic_update_slice"),
    (34, 35, "%fusion.11 = f32[2,8512]{} fusion()", ""),
    (35, 37, "%conditional.2 = (f32[2,2048]{}) conditional(pred[] %p)",
     "jit(step)/while/body/cond"),
    (35, 36, "%fusion.12 = f32[2,32,1024]{} fusion()", ""),
    (37, 38, _gemm("gemm_packed_fused_a.58", 2),
     "jit(step)/while/body/mlp/gemm_packed_fused_a/pallas_call"),
    (38, 39, "%fusion.13 = f32[36,2,3,4352]{} fusion()",
     "jit(step)/while/body/mlp/add"),
]


def _context(tmp_path, ops=STEP_OPS, model=M):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops=ops, host=HOST_SPANS))
    arch = full_arch()[0] if model is M else __import__(
        "chipbench.tests.test_chipbench_program",
        fromlist=["ARCH"]).ARCH
    return T.Context(trace=T.reduce_profile(ProfileData.from_file(str(path))),
                     model=model, arch=arch, serving=SERVING, peaks=PEAKS,
                     prompt_len={7: 2, 8: 3},
                     calls=[(1.011, 2, [(7, 1), (8, 4)])],
                     ticks=[(1.0, 1.012)], host_window=(0.9, 1.1))


def test_readers_of_the_recurrent_update(tmp_path):
    ctx = _context(tmp_path)
    state_ms = cell.metric_reader("ssm_state_ms.decode")
    roofline = cell.metric_reader("ssm_roofline.decode")
    assert len(ctx.step_modules()) == 1
    # 30-31, 31-33 and 33-34; the loop holding them is left out.
    assert state_ms.read(ctx, str(tmp_path)) == pytest.approx(4.0)
    arch = ctx.arch
    least = max(2 * 36 * (6 * 64 * 64 * 128 + 2 * 4 * 4352) / 197e12,
                2 * 4 * 2 * 36 * (64 * 64 * 128 + 3 * 4352) / 819e9)
    assert least == flops.ideal_s(M.ssm_state_flops(arch, 2),
                                  M.ssm_state_bytes(arch, 2), 197e12, 819e9)
    assert roofline.read(ctx, str(tmp_path)) == pytest.approx(
        100 * least / 4e-3)


def test_readers_find_nothing_without_a_recurrent_state(tmp_path):
    from chipbench.arch import dense
    from chipbench.tests.test_chipbench_program import STEP_OPS as DENSE_OPS
    ctx = _context(tmp_path, ops=DENSE_OPS, model=dense)
    assert cell.metric_reader("ssm_state_ms.decode").read(
        ctx, str(tmp_path)) is None
    assert cell.metric_reader("ssm_roofline.decode").read(
        ctx, str(tmp_path)) is None
    # The hybrid's trace read in another run's directory: nothing.
    ctx = _context(tmp_path)
    assert cell.metric_reader("ssm_state_ms.decode").read(
        ctx, str(tmp_path / "none")) is None
