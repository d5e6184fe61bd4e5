"""Serving driver: load a checkpoint (or random-init), serve batched requests.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --requests 8 \
      --prompt-len 16 --new 32 [--ckpt-dir /tmp/ckpt]

Demonstrates the production serving path on the host devices: jit'd prefill +
decode programs, device-resident caches, request batching, throughput report.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.launch.train import preset_config
from repro.models import build
from repro.serve.engine import Engine, ServeConfig
from repro.train import checkpoint as ckpt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = preset_config(args.arch, args.preset)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        restored, step = ckpt.restore(args.ckpt_dir, {"params": params})
        params = restored["params"]
        print(f"loaded checkpoint step {step}")

    engine = Engine(model, params, ServeConfig(
        max_len=args.prompt_len + args.new + 8,
        temperature=args.temperature))

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.requests, args.prompt_len)),
        jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(args.requests, cfg.num_patches, cfg.d_model)),
            jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.requests, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)

    # warm (compile) then measure steady-state decode throughput
    engine.generate(batch, max_new_tokens=2)
    t0 = time.time()
    out = engine.generate(batch, max_new_tokens=args.new)
    dt = time.time() - t0
    print(f"arch={cfg.name} requests={args.requests} "
          f"prompt={args.prompt_len} new={args.new}")
    print(f"steady-state: {args.requests * args.new / dt:.1f} tok/s "
          f"({dt / args.new * 1e3:.1f} ms/decode-step)")
    print("first request:", out[0][:16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
