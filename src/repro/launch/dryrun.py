import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU emulation: never take a chip

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, compiles,
fits memory, and extract the roofline terms from the compiled artifact.

The three lines above MUST run before any other import (jax locks the device
count and platform at first init); do not move them. The platform pin keeps
this tool, and the children ``--all`` starts (they inherit it), off any
attached accelerator. This module is the ONLY place the
512-device emulation is enabled — tests and benches see the real host.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell, subprocess each
  python -m repro.launch.dryrun --list
Results land incrementally in results/dryrun/<arch>--<shape>--<mesh>.json.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_configs, get_config
from repro.configs.shapes import SHAPES, iter_cells, shape_applicability
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.parallel import sharding as shard_rules
from repro.parallel.mesh import use_mesh
from repro.roofline import analysis
from repro.train import optimizer as opt
from repro.train.loop import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _model_flops(cfg, shape) -> float:
    n = cfg.active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_cell(arch: str, shape_name: str, mesh_kind: str,
               decode_params_mode: str = "2d", serve_dtype: str = "bf16"):
    """Returns (jit_fn, example_args) ready to .lower()."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = shape_applicability(cfg, shape)
    if skip:
        raise RuntimeError(f"cell skipped by assignment: {skip}")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    model = build(cfg)
    params = specs_mod.params_specs(model)
    p_specs = shard_rules.param_specs(cfg, params, mesh)

    if shape.kind == "train":
        batch = specs_mod.train_batch_specs(cfg, shape)
        b_specs = shard_rules.batch_specs(batch, mesh)
        opt_state = jax.eval_shape(opt.init_state, params)
        o_specs = {"mu": p_specs, "nu": p_specs, "step": P()}
        # 1M-token steps run as microbatched gradient accumulation: bounds the
        # per-pass activation tree (EXPERIMENTS.md §Perf). Per-layer collective
        # traffic scales with the microbatch count, so use the SHALLOWEST
        # accumulation that fits: 8 only for the SSD mixers (fat chunk
        # tensors), 4 elsewhere (§Perf H2).
        if shape.global_batch * shape.seq_len >= 2 ** 20:
            micro = 8 if cfg.has_ssm else 4
        else:
            micro = 1
        step = make_train_step(model, TrainConfig(microbatches=micro))
        fn = jax.jit(
            step,
            in_shardings=(_named(mesh, p_specs), _named(mesh, o_specs),
                          _named(mesh, b_specs)),
            out_shardings=(_named(mesh, p_specs), _named(mesh, o_specs),
                           None),
            donate_argnums=(0, 1))
        args = (params, opt_state, batch)
    elif shape.kind == "prefill":
        batch = specs_mod.train_batch_specs(cfg, shape)
        batch.pop("labels")
        b_specs = shard_rules.batch_specs(batch, mesh)
        fn = jax.jit(
            lambda p, bt: model.prefill(p, bt, max_len=shape.seq_len,
                                        cache_dtype=jnp.bfloat16),
            in_shardings=(_named(mesh, p_specs), _named(mesh, b_specs)))
        args = (params, batch)
    else:  # decode
        # Serving deployments load bf16 (or int8-quantized) weights:
        # replicating f32 masters across the FSDP axis would blow HBM.
        serve_dt = jnp.int8 if serve_dtype == "int8" else jnp.bfloat16

        def _serve_dtype(s):
            if s.ndim >= 2 and s.dtype == jnp.float32:
                return jax.ShapeDtypeStruct(s.shape, serve_dt)
            return s

        params = jax.tree.map(_serve_dtype, params)
        caches, token, pos = specs_mod.decode_state_specs(model, cfg, shape)
        c_specs = shard_rules.cache_specs(cfg, caches, mesh)
        # Default "2d": bf16 weights keep the (data x model) 2-D layout —
        # XLA reduces the tiny one-token activations across "data" instead of
        # gathering weights, so decode gets weight memory /256 with near-zero
        # collective cost. "tp_only" replicates across data (measured
        # variant); "fsdp" is the f32 baseline kept for §Perf before/after.
        if decode_params_mode == "tp_only":
            # hillclimb variant: replicate over data axis (no per-step FSDP
            # all-gather), TP sharding kept.
            def _drop_data(spec: P) -> P:
                parts = []
                for ax in spec:
                    if isinstance(ax, tuple):
                        kept = tuple(a for a in ax if a != "data")
                        parts.append(kept if kept else None)
                    else:
                        parts.append(None if ax == "data" else ax)
                return P(*parts)

            p_specs = jax.tree.map(_drop_data, p_specs,
                                   is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(
            model.decode,
            in_shardings=(_named(mesh, p_specs), _named(mesh, c_specs),
                          NamedSharding(mesh, shard_rules.batch_specs(
                              token, mesh)),
                          NamedSharding(mesh, shard_rules.batch_specs(
                              pos, mesh))),
            donate_argnums=(1,))
        args = (params, caches, token, pos)
    return cfg, shape, mesh, fn, args


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = RESULTS_DIR, force: bool = False,
             decode_params_mode: str = "2d", serve_dtype: str = "bf16",
             tag: str = "") -> dict:
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"--{tag}" if tag else ""
    out_path = os.path.join(out_dir,
                            f"{arch}--{shape_name}--{mesh_kind}{suffix}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    result: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "tag": tag, "status": "running"}
    t0 = time.time()
    try:
        cfg, shape, mesh, fn, args = build_cell(arch, shape_name, mesh_kind,
                                                decode_params_mode,
                                                serve_dtype)
        with use_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            mem = compiled.memory_analysis()
            print(compiled.memory_analysis())
            cost = compiled.cost_analysis()
            print({k: cost[k] for k in ("flops", "bytes accessed")
                   if k in cost})
        roof = analysis.analyze(
            compiled, arch=arch, shape=shape_name, mesh_name=mesh_kind,
            chips=mesh.devices.size, model_flops=_model_flops(cfg, shape),
            compute_dtype="bfloat16")
        peak_raw = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        # CPU-backend artifact: f32 widenings of bf16 data (see
        # roofline.analysis.cpu_bf16_emulation_bytes) do not exist on TPU.
        emu = analysis.cpu_bf16_emulation_bytes(compiled.as_text())
        live = mem.argument_size_in_bytes + mem.output_size_in_bytes \
            - mem.alias_size_in_bytes
        peak_tpu = max(peak_raw - emu, live)
        result.update(
            status="ok",
            chips=int(mesh.devices.size),
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_compile - t_lower, 2),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                peak_per_device=peak_raw,
                cpu_bf16_emulation_bytes=emu,
                peak_per_device_tpu_estimate=peak_tpu,
            ),
            roofline=roof.to_dict(),
        )
        result["fits_hbm_raw"] = bool(peak_raw <= analysis.V5E.hbm_bytes)
        result["fits_hbm"] = bool(peak_tpu <= analysis.V5E.hbm_bytes)
    except Exception as e:  # noqa: BLE001 — recorded, cell marked failed
        result.update(status="failed", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    result["wall_s"] = round(time.time() - t0, 2)
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=2, default=str)
    os.replace(out_path + ".tmp", out_path)
    status = result["status"]
    print(f"[{status:6s}] {arch} x {shape_name} x {mesh_kind}{suffix} "
          f"({result['wall_s']}s)")
    return result


def all_cells():
    for cfg, shape, skip in iter_cells(all_configs()):
        for mesh_kind in ("single", "multi"):
            yield cfg.name, shape.name, mesh_kind, skip


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--serve-dtype", default="bf16",
                    choices=("bf16", "int8"))
    ap.add_argument("--decode-params", default="2d",
                    help="fsdp variant kept for the §Perf before/after",
                    choices=("fsdp", "tp_only", "2d"))
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)

    if args.list:
        for arch, shp, mesh_kind, skip in all_cells():
            note = f"SKIP ({skip})" if skip else "run"
            print(f"{arch:26s} {shp:12s} {mesh_kind:7s} {note}")
        return 0

    if args.all:
        failures = 0
        for arch, shp, mesh_kind, skip in all_cells():
            if skip:
                continue
            out_path = os.path.join(
                args.out, f"{arch}--{shp}--{mesh_kind}.json")
            if os.path.exists(out_path) and not args.force:
                with open(out_path) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"[cached] {arch} x {shp} x {mesh_kind}")
                        continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shp, "--mesh", mesh_kind,
                   "--out", args.out]
            if args.force:
                cmd.append("--force")
            try:
                rc = subprocess.run(cmd, timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = -1
                print(f"[timeout] {arch} x {shp} x {mesh_kind}")
            failures += (rc != 0)
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all/--list)"
    result = run_cell(args.arch, args.shape, args.mesh, args.out,
                      force=args.force, decode_params_mode=args.decode_params,
                      serve_dtype=args.serve_dtype, tag=args.tag)
    return 0 if result["status"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
