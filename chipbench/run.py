#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``: model sizes and serving settings) and a
traffic mix (``chipbench/traffic/<traffic>.json``). Set-up builds the served
weights from the seed on the device, builds the engine and scheduler, and
serves one request of each prompt length so that every program of the
window is compiled (or loaded from the persistent cache in
``<checkout>/.jax_cache``) before it opens. Then the traffic (open loop, or
a closed loop's backlog) runs for a lead-in and the window, the requests in
flight drain, peak device memory is read, the program's state is freed, and
the float32 reference checks a sample of the served requests.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
few seconds of the window with the JAX profiler and reports its per-layer
metrics (each read by ``chipbench/metrics/<metric>.py``). The last line of
standard output is one JSON object. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import cell as cells  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n:
        sys.exit(f"chipbench: the cell needs {n} TPU chips, found "
                 f"{len(devices)}")
    return devices


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, otherwise the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1].strip())
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cells.load(ROOT, args.workload)
    devices = require_chips(spec.chips)
    cache = enable_cache(ROOT)
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell "
        f"{spec.name}: {spec.config_name} under {spec.traffic_name}; "
        f"compile cache {cache}")
    result = cells.run(spec, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), device=devices[0],
                       process_start=PROCESS_START, log=log,
                       trace_dir=os.path.join(ROOT, ".chipbench", "trace"))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
