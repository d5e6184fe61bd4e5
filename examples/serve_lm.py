"""Serving example: the jit'd engine, batched or as a request stream.

  PYTHONPATH=src python examples/serve_lm.py --arch olmo-1b --batch 4 --new 24
  PYTHONPATH=src python examples/serve_lm.py --stream --batch 12
  PYTHONPATH=src python examples/serve_lm.py --stream --continuous --batch 12

Trains nothing — serves random-init weights to demonstrate the serving
paths: static batched decode (default), or ``--stream``, which offers the
same requests as a Poisson arrival stream to the resilient front-end
(bounded admission queue with typed ``Overloaded`` shedding, per-request
deadlines, retry-with-backoff, per-request fault isolation); add
``--continuous`` to serve the stream through the slot-recycling
continuous-batching scheduler instead (one shared batched decode program
over a paged KV pool, preempt/resume under block exhaustion). Both stream
modes end by printing ``Engine.serve_report()`` and
``Engine.health_report()`` — the lifecycle/health registries every
production deployment would scrape.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import reduced_config
from repro.models import build
from repro.serve import (ContinuousConfig, ContinuousScheduler, Engine,
                         Request, ServeConfig, StreamConfig, StreamFrontend)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pack-weights", action="store_true",
                    help="tile-major pack all dense weights at load time "
                         "(fused pack-free-A GEMM on every step)")
    ap.add_argument("--quantize", default=None,
                    choices=("int8", "int8:col", "int4", "int4:col"),
                    help="quantize the packed weights at load (int8 or "
                         "nibble-packed int4 tiles; ':col' hoists dequant to "
                         "a per-column store epilogue; implies "
                         "--pack-weights)")
    ap.add_argument("--stream", action="store_true",
                    help="serve a Poisson request stream through the "
                         "resilient front-end instead of one static batch")
    ap.add_argument("--continuous", action="store_true",
                    help="with --stream: serve through the slot-recycling "
                         "continuous-batching scheduler (shared batched "
                         "decode over a paged KV pool) instead of the "
                         "batch-1 front-end")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(
        max_len=args.prompt_len + args.new + 8,
        temperature=args.temperature,
        pack_weights=args.pack_weights or args.quantize is not None,
        quantize=args.quantize))

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.num_patches, cfg.d_model)),
            jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)

    if args.stream:
        if cfg.family in ("vlm", "audio"):
            raise SystemExit("--stream demo serves token-LM requests only")
        rng_s = np.random.default_rng(1)
        reqs = [Request(request_id=i,
                        tokens=rng_s.integers(
                            0, cfg.vocab_size,
                            int(rng_s.choice((4, args.prompt_len))))
                        .astype(np.int32),
                        max_new_tokens=args.new,
                        deadline_s=30.0)
                for i in range(args.batch)]
        schedule = [(float(t), r) for t, r in
                    zip(np.cumsum(rng_s.exponential(0.05, len(reqs))), reqs)]
        if args.continuous:
            block = next(b for b in (16, 8, 4, 2, 1)
                         if engine.cfg.max_len % b == 0)
            server = ContinuousScheduler(engine, ContinuousConfig(
                queue_capacity=max(2, args.batch // 2), max_live=4,
                block_size=block))
        else:
            server = StreamFrontend(engine, StreamConfig(
                queue_capacity=max(2, args.batch // 2), max_live=4))
        t0 = time.time()
        results = server.run(schedule)
        dt = time.time() - t0
        toks = sum(len(r.tokens) for r in results.values() if r.ok)
        mode = "continuous" if args.continuous else "batch-1"
        print(f"arch={cfg.name} stream={len(reqs)} reqs ({mode}) "
              f"new<={args.new}: {toks} tokens in {dt:.2f}s")
        for rid in sorted(results):
            r = results[rid]
            print(f"  req{rid}: {r.status:13s} lat={r.latency_s:6.2f}s "
                  f"{r.tokens.tolist() if len(r.tokens) else r.detail}")
        print("lifecycle counters:", server.stats())
        # The registries a production deployment would scrape: the
        # request-lifecycle report (conservation counters + per-request
        # records) and the dispatch-health degradation report.
        print("serve_report:",
              json.dumps(engine.serve_report(), indent=2, default=str))
        health = engine.health_report()
        print("health_report:",
              json.dumps(health, indent=2, default=str) if health
              else "{} (healthy: no degraded lowerings)")
        return

    t0 = time.time()
    out = engine.generate(batch, max_new_tokens=args.new)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new}")
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch*args.new/dt:.1f} tok/s incl. compile)")
    for i, row in enumerate(out):
        print(f"  req{i}: {row.tolist()}")


if __name__ == "__main__":
    main()
