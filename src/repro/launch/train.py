"""End-to-end training driver.

Runs on whatever devices the host has (CPU for the examples; the same code
path pjit-shards on a real mesh). Features exercised: deterministic data
pipeline, mixed precision, AdamW, checkpoint/auto-resume (fault tolerance),
straggler monitoring, elastic restore (checkpoints are mesh-agnostic).

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --preset tiny \
      --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.data.pipeline import DataConfig, MarkovLM, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.parallel import sharding as shard_rules
from repro.parallel.mesh import use_mesh
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt
from repro.train.loop import StragglerMonitor, TrainConfig, make_train_step
from repro.train.optimizer import AdamWConfig

PRESETS = {
    # name: (d_model, layers, heads, d_ff, vocab) — ~param count targets
    "tiny": (128, 4, 4, 512, 512),        # ~1M: CI / smoke
    "small": (256, 6, 8, 1024, 2048),     # ~8M: CPU example
    "100m": (768, 12, 12, 3072, 32000),   # ~124M: the assignment's e2e size
}


def preset_config(arch: str, preset: str):
    cfg = reduced_config(arch) if preset == "tiny" else get_config(arch)
    if preset in PRESETS:
        d, l, h, f, v = PRESETS[preset]
        kvh = min(cfg.num_kv_heads, h) or h
        if h % max(kvh, 1):
            kvh = h
        cfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-{preset}", num_layers=l, d_model=d,
            num_heads=h if cfg.num_heads else 0,
            num_kv_heads=kvh if cfg.num_heads else 0,
            head_dim=(d // h) if cfg.num_heads else 0,
            d_ff=0 if cfg.d_ff == 0 else f, vocab_size=v,
            num_experts=min(cfg.num_experts, 4),
            num_experts_per_tok=min(cfg.num_experts_per_tok, 2),
            ssm_state_size=min(cfg.ssm_state_size, 32),
            ssm_head_dim=32 if cfg.ssm_state_size else cfg.ssm_head_dim,
            encoder_seq=64 if cfg.is_encoder_decoder else 0,
            encoder_layers=2 if cfg.is_encoder_decoder else 0,
            num_patches=16 if cfg.num_patches else 0,
            sliding_window=256 if cfg.sliding_window else None,
            compute_dtype="float32",
        )
    return cfg


def init_sharded(model, mesh, key):
    """Parameters and AdamW state, created under jit straight into their
    mesh shardings (``parallel.sharding`` rules) — never materialized on
    one device first, which at full width would not fit a single chip."""
    params_shape = jax.eval_shape(model.init, key)
    p_sh = shard_rules.named_shardings(model.cfg, params_shape, mesh)
    o_sh = {"mu": p_sh, "nu": p_sh, "step": NamedSharding(mesh, P())}

    def init(k):
        params = model.init(k)
        return params, opt.init_state(params)

    with use_mesh(mesh):
        return jax.jit(init, out_shardings=(p_sh, o_sh))(key)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS) + ["full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="markov", choices=("markov", "uniform"))
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--grad-compression", default=None, choices=(None, "bf16"))
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = preset_config(args.arch, args.preset)
    model = build(cfg)
    mesh = make_host_mesh(args.model_parallel)
    print(f"arch={cfg.name} params≈{cfg.num_params()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} devices={jax.device_count()}")

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    data = MarkovLM(data_cfg) if args.data == "markov" else SyntheticLM(data_cfg)

    train_cfg = TrainConfig(
        optim=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          total_steps=args.steps),
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        remat=True)
    step_fn = make_train_step(model, train_cfg)

    params, opt_state = init_sharded(model, mesh, jax.random.PRNGKey(0))
    with use_mesh(mesh):
        start_step = 0
        if args.ckpt_dir:
            latest = ckpt.latest_valid_step(args.ckpt_dir)
            if latest is not None:
                state, start_step = ckpt.restore(
                    args.ckpt_dir, {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                print(f"resumed from checkpoint step {start_step}")

        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        monitor = StragglerMonitor()
        history = []
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
            monitor.start()
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if (step + 1) % args.log_every == 0 or step == start_step:
                m = {k: float(v) for k, v in metrics.items()}
                history.append({"step": step + 1, **m})
                print(f"step {step+1:5d} loss={m['loss']:.4f} "
                      f"acc={m['accuracy']:.3f} gnorm={m['grad_norm']:.2f} "
                      f"lr={m['lr']:.2e}")
            slow = monitor.stop(step)
            if slow:
                print(f"  [straggler-monitor] step {step} exceeded EWMA "
                      f"threshold")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step + 1,
                          {"params": params, "opt": opt_state})
                ckpt.cleanup(args.ckpt_dir, keep_last=3)

        dt = time.time() - t_start
        steps_done = args.steps - start_step
        if args.ckpt_dir and steps_done:
            ckpt.save(args.ckpt_dir, args.steps,
                      {"params": params, "opt": opt_state})
        print(f"done: {steps_done} steps in {dt:.1f}s "
              f"({dt/max(steps_done,1)*1000:.0f} ms/step); "
              f"straggler flags: {len(monitor.flagged)}")
        if args.metrics_out and history:
            with open(args.metrics_out, "w") as f:
                json.dump(history, f, indent=2)
        if history:
            first, last = history[0]["loss"], history[-1]["loss"]
            print(f"loss: {first:.4f} -> {last:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
