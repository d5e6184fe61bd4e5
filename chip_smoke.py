#!/usr/bin/env python3
"""Smoke run of the layered-GEMM system on a TPU, through its user entry points.

  python chip_smoke.py                # one chip: olmo-1b serving, packed weights
  python chip_smoke.py --four-chips   # 2x2 host: olmo-1b sharded training

One chip (default): olmo-1b at full width (16 layers, d_model 2048, d_ff 8192,
vocab 50304) with random weights from ``--seed``, served by ``Engine`` with
load-time packed weights (the fused Pallas GEMMs), then again with int8
packed weights. Each phase prefills and greedily decodes a batch of requests
and is checked against the same parameters run through the library lowering
(``xla``, chosen by name): prefill logits within a stated share of the logit
scale, and the first greedy token of every row.

Four chips (``--four-chips``, only that phase): sharded training as
``repro.launch.train --model-parallel 2`` runs it. olmo-1b cut to 2 layers
trains 3 steps on a one-device mesh and on a (data 2 x model 2) mesh, whose
per-step losses must agree; then full-depth olmo-1b trains 3 steps on the
2x2 mesh with finite losses and each device's peak memory printed.

Every phase fails the run on any error: a non-empty ``Engine.health_report()``,
a reference lowering in ``Engine.dispatch_report``, or a decode program with
no Pallas kernel in it. Without a TPU the script exits non-zero and prints no
result. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Rates printed here are this smoke run's own, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

BATCH, PROMPT, NEW = 4, 128, 16
# Prefill logits, as a share of the logit scale (max |reference logit|):
LOGIT_TOL = 0.05      # served vs xla on the same weights, both in bf16 (the
                      # int8 engine bound of tests/test_quant_gemm.py)
F32_RATIO, F32_SLACK = 1.5, 0.005   # served's error against the float32
                      # reference <= 1.5x xla-bf16's own error against it
                      # + 0.5% of the scale: the kernels lose no more than
                      # the library does at the same dtype
LOSS_TOL = 0.02       # one-device vs 2x2 mesh training loss, absolute
REFERENCE_LOWERINGS = ("xla", "jnp_ref", "grouped_jnp_ref")


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devices)}")
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats().get("peak_bytes_in_use", -1))


def prompts(vocab: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, vocab, (BATCH, PROMPT)),
                                  jnp.int32)}


def served_weights(params):
    """The served tree with every packed weight unpacked (and dequantized)
    to the natural [K, N] float32 array it multiplies by — the same
    parameters, in the layout the library lowering takes."""
    from repro.core.layered import PackedWeight
    from repro.kernels.ref import unpack_b_dequant_ref

    def unpack(w):
        if not isinstance(w, PackedWeight):
            return w

        def one(packed, scales):
            return unpack_b_dequant_ref(packed, scales, w.k, w.n,
                                        w.fmt.layout, fmt=w.fmt
                                        ).astype(jnp.float32)
        if w.packed.ndim == 5:   # scan-stacked [L, Nb, Kb, t0, t1]
            return jax.vmap(one)(w.packed, w.scales)
        return one(w.packed, w.scales)

    return jax.jit(lambda p: jax.tree.map(
        unpack, p, is_leaf=lambda x: isinstance(x, PackedWeight)))(params)


def xla_prefill(model, params, batch, max_len):
    """Prefill logits with every GEMM on the library lowering, chosen by
    name through the dispatch override."""
    from repro.core import ContractionSpec, dispatch
    os.environ["REPRO_GEMM_STRATEGY"] = "xla"
    try:
        cfg = model.cfg
        spec = ContractionSpec.dense(BATCH * PROMPT, cfg.d_model,
                                     cfg.vocab_size, cfg.compute_dtype)
        assert dispatch(spec).name == "xla", dispatch(spec).name
        fn = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len)[0])
        return np.asarray(fn(params, batch))
    finally:
        del os.environ["REPRO_GEMM_STRATEGY"]


def references(model, weights, batch, max_len) -> dict:
    """xla's prefill logits on ``weights`` at the serving compute dtype
    (bf16), and in float32 at the highest matmul precision (the plain
    reference)."""
    from repro.models import build
    f32 = build(dataclasses.replace(model.cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref_f32 = xla_prefill(f32, weights, batch, max_len)
    return {"bf16": xla_prefill(model, weights, batch, max_len),
            "f32": ref_f32}


def rel_err(got, ref) -> float:
    """Max |got - ref| over the logit scale (max |ref|)."""
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def check_logits(name, got, refs) -> None:
    """Served prefill logits against xla on the same weights: within
    LOGIT_TOL of the bf16 lowering, no further from the float32 reference
    than F32_RATIO x the bf16 lowering is (+ F32_SLACK), and the first
    greedy token of every row equal — unless the reference's own top-2
    margin is inside the tolerance (a near-tie either lowering may break)."""
    ref = refs["bf16"]
    err, err32 = rel_err(got, ref), rel_err(got, refs["f32"])
    lib32 = rel_err(ref, refs["f32"])
    bound32 = F32_RATIO * lib32 + F32_SLACK
    log(f"[{name}] prefill logits err/scale: vs xla-bf16 {err:.6g} "
        f"(tolerance {LOGIT_TOL}); vs xla-f32 {err32:.6g} (tolerance "
        f"{bound32:.6g}; xla-bf16 itself {lib32:.6g})")
    if not err <= LOGIT_TOL:
        raise AssertionError(f"{name}: logits off by {err:.4g} of the logit "
                             f"scale (> {LOGIT_TOL})")
    if not err32 <= bound32:
        raise AssertionError(f"{name}: float32 error {err32:.4g} > "
                             f"{bound32:.4g}")
    scale = float(np.abs(ref).max())
    want, have = ref.argmax(-1), got.argmax(-1)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    for row in range(ref.shape[0]):
        margin = float(top2[row, 1] - top2[row, 0])
        if want[row] != have[row] and margin > LOGIT_TOL * scale:
            raise AssertionError(f"{name}: row {row} first token {have[row]} "
                                 f"!= reference {want[row]}")
        log(f"[{name}] row {row}: first token {have[row]} "
            f"(reference {want[row]}, reference top-2 margin {margin:.4g})")


def serve_phase(name, model, params, batch, *, quantize, max_len, device,
                float_ref=None):
    """Serve ``params`` through ``Engine`` with packed weights and check it;
    returns xla's bf16 prefill logits on the served weights."""
    from repro.serve.engine import Engine, ServeConfig
    t0 = time.perf_counter()
    engine = Engine(model, params, ServeConfig(
        max_len=max_len, pack_weights=True, quantize=quantize))
    jax.block_until_ready(engine.params)
    log(f"[{name}] load-time packing: {time.perf_counter() - t0:.2f} s")
    bad = {k: v for k, v in engine.dispatch_report.items()
           if v in REFERENCE_LOWERINGS}
    if bad:
        raise AssertionError(f"{name}: reference lowerings dispatched: {bad}")
    t0 = time.perf_counter()
    refs = references(model, served_weights(engine.params), batch, max_len)
    log(f"[{name}] xla references on the served weights: "
        f"{time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    tokens = engine.generate(batch, max_new_tokens=NEW)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.generate(batch, max_new_tokens=NEW)
    warm_s = time.perf_counter() - t0
    log(f"[{name}] generate {tokens.shape}: first call {first_s:.2f} s, warm "
        f"{warm_s:.2f} s, compile ~{first_s - warm_s:.2f} s")
    log(f"[{name}] row 0 tokens: {tokens[0].tolist()}")

    logits, caches = engine._prefill(engine.params, batch)
    logits = np.asarray(logits)
    check_logits(name, logits, refs)
    if float_ref is not None:
        log(f"[{name}] vs xla-bf16 on the float weights (the quantization's "
            f"own cost): err/scale {rel_err(logits, float_ref):.6g}")

    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((BATCH,), PROMPT, jnp.int32)
    text = engine._decode.lower(engine.params, caches, tok,
                                pos).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{name}: decode program holds no Pallas kernel")
    jax.block_until_ready(engine._decode(engine.params, caches, tok, pos))
    t0 = time.perf_counter()
    for i in range(NEW):
        step_logits, caches = engine._decode(engine.params, caches, tok,
                                             pos + i)
        tok = engine.sample_tokens(step_logits[:, 0], jnp.arange(BATCH),
                                   i + 1)[:, None]
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    log(f"[{name}] smoke decode rate (this smoke run on the chip, not a "
        f"benchmark): {BATCH * NEW / dt:.1f} tok/s, "
        f"{dt / NEW * 1e3:.2f} ms/step at batch {BATCH}")

    health = engine.health_report()
    if health:
        raise AssertionError(f"{name}: degraded lowerings: {health}")
    log(f"[{name}] health report: empty; decode program has tpu_custom_call; "
        f"peak_bytes_in_use={peak_bytes(device)}")
    return refs["bf16"]


def serving(seed: int, device) -> None:
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("olmo-1b")
    model = build(cfg)
    max_len = PROMPT + NEW
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"[init] olmo-1b {cfg.num_layers}L d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}: "
        f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f} B params "
        f"in {time.perf_counter() - t0:.2f} s")
    batch = prompts(cfg.vocab_size, seed)
    float_ref = serve_phase("packed-bf16", model, params, batch,
                            quantize=None, max_len=max_len, device=device)
    serve_phase("packed-int8", model, params, batch, quantize="int8",
                max_len=max_len, device=device, float_ref=float_ref)


def train_losses(model, mesh, seed: int, steps: int = 3):
    from repro.data.pipeline import DataConfig, MarkovLM
    from repro.launch.train import init_sharded
    from repro.parallel.mesh import use_mesh
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.optimizer import AdamWConfig
    params, opt_state = init_sharded(model, mesh, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(model, TrainConfig(optim=AdamWConfig(
        lr=1e-4, warmup_steps=1, total_steps=steps))), donate_argnums=(0, 1))
    data = MarkovLM(DataConfig(vocab_size=model.cfg.vocab_size, seq_len=PROMPT,
                               global_batch=8, seed=seed))
    losses = []
    with use_mesh(mesh):
        for i in range(steps):
            t0 = time.perf_counter()
            batch = jax.tree.map(jnp.asarray, data.batch_at(i))
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            log(f"  step {i + 1}: loss={losses[-1]:.6f} "
                f"({time.perf_counter() - t0:.2f} s incl. compile)")
    return losses


def four_chips(seed: int, devices) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import compat_make_mesh
    from repro.models import build
    axes = ("data", "model")
    one = compat_make_mesh((1, 1), axes, devices=devices[:1])
    grid = compat_make_mesh((2, 2), axes, devices=devices[:4])
    full = get_config("olmo-1b")
    cut = build(dataclasses.replace(full, num_layers=2))
    log("[train 2L] one-device mesh")
    l1 = train_losses(cut, one, seed)
    log("[train 2L] 2x2 mesh (data 2 x model 2)")
    l4 = train_losses(cut, grid, seed)
    diff = max(abs(a - b) for a, b in zip(l1, l4))
    log(f"[train 2L] max |loss(1 chip) - loss(2x2)| = {diff:.6g} "
        f"(tolerance {LOSS_TOL})")
    if not diff <= LOSS_TOL:
        raise AssertionError(f"1-chip and 2x2 losses differ: {l1} vs {l4}")
    log("[train 16L] full-depth olmo-1b on the 2x2 mesh")
    losses = train_losses(build(full), grid, seed)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite losses {losses}")
    for d in devices[:4]:
        log(f"[train 16L] {d}: peak_bytes_in_use={peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on a 2x2 host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache: "
        f"{cache} ({'warm' if warm else 'cold'} at start)")
    if args.four_chips:
        four_chips(args.seed, devices)
    else:
        serving(args.seed, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
