#!/usr/bin/env python3
"""Readings the benchmark's limits, rates and recorded trace come from.

The benchmark's own runs never run this. On a machine with the chip:

  python chipbench/calibrate.py readings --workload <cell> --seeds 1,2,3 \\
      --seconds 10 [--control int8 | --fault token_altered]
  python chipbench/calibrate.py sweep --workload <cell> --rates 1,2,3 \\
      --seconds 30 --seed 7 [--write-rate 0.8]
  python chipbench/calibrate.py record --workload <cell> --seed 7 \\
      --seconds 4 --trace-seconds 0.5 --out <file.json.gz>

``readings`` serves the cell once per seed in one process and prints each
run's compared numbers against the float32 reference; ``--control int8``
switches on the program's own int8 weight path, the precision below the
bfloat16 the configurations state, whose readings must fail the limit;
``--fault <name>`` plants one of ``chipbench.faults`` under the timed path.
``sweep`` serves the cell's mix at each rate and prints what decides
whether a rate is sustained (nothing shed, and the queue at the window's
end no longer than at its middle); ``--write-rate f`` then writes ``f``
times the highest sustained rate into the cell's traffic file. ``record``
makes one traced run and keeps its reduced trace (and the raw profile when
it is small) in ``--out``. ``--traffic '{...}'`` overrides keys of the mix
and ``--config <name>`` serves another configuration file (with the
architecture module it names) under it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import cell  # noqa: E402
from chipbench.run import enable_cache, require_chips  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")


def _quiet(_msg: str) -> None:
    pass


def _line(spec, seed, out, t, **extra) -> str:
    return json.dumps(dict(
        workload=spec.name, seed=seed, failed=out["failed"],
        attempted=out["attempted"], compared=out["compared"],
        metrics={k: v["value"] for k, v in out["metrics"].items()},
        wall_s=time.perf_counter() - t, **extra))


def readings(spec, seeds, seconds, control, device, fault=None) -> None:
    if fault:
        from chipbench.faults import FAULTS
        FAULTS[fault](setattr)
    for seed in seeds:
        t, stats = time.perf_counter(), {}
        out = cell.run(spec, seed=seed, seconds=seconds, trace=False,
                       device=device, process_start=t, log=_quiet,
                       trace_dir=TRACE_DIR, quantize=control, stats=stats)
        check = stats["check"]
        print(_line(spec, seed, out, t, control=control, fault=fault,
                    logit_err=check["logit_err"], tokens=check["tokens"],
                    rows=sorted(stats["sample_rows"])), flush=True)


def sustained(stats: dict) -> bool:
    return stats["shed"] == 0 and \
        (stats["queue_end"] or 0) <= (stats["queue_mid"] or 0)


def sweep(spec, rates, seconds, seed, device, write_rate) -> None:
    from chipbench.generator import closed_loop
    if closed_loop(spec.traffic):
        raise SystemExit(f"{spec.name}: a closed loop has no rate to sweep")
    best = None
    for rate in rates:
        s = dataclasses.replace(spec, traffic=dict(spec.traffic,
                                                   rate_per_s=rate))
        t, stats = time.perf_counter(), {}
        out = cell.run(s, seed=seed, seconds=seconds, trace=False,
                       device=device, process_start=t, log=_quiet,
                       trace_dir=TRACE_DIR, stats=stats)
        ok = sustained(stats)
        if ok:
            best = rate if best is None else max(best, rate)
        print(_line(s, seed, out, t, rate_per_s=rate, sustained=ok,
                    window=stats), flush=True)
    print(json.dumps({"workload": spec.name, "highest_sustained": best}),
          flush=True)
    if write_rate and best is not None:
        path = os.path.join(cell.HERE, "traffic", spec.traffic_name + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix["rate_per_s"] = round(write_rate * best, 3)
        with open(path, "w") as f:
            json.dump(mix, f, indent=1)
            f.write("\n")
        print(json.dumps({"wrote": path, "rate_per_s": mix["rate_per_s"]}),
              flush=True)


def record(spec, seed, seconds, trace_seconds, out_path, device) -> None:
    t = time.perf_counter()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    out = cell.run(spec, seed=seed, seconds=seconds, trace=True,
                   device=device, process_start=t, log=print,
                   trace_dir=TRACE_DIR, trace_seconds=trace_seconds,
                   keep_trace=out_path)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "sweep", "record"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--write-rate", type=float, default=0.0)
    ap.add_argument("--trace-seconds", type=float, default=0.5)
    ap.add_argument("--out", default=os.path.join(ROOT, ".chipbench",
                                                  "trace.json.gz"))
    ap.add_argument("--traffic", default="{}")
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)
    spec = cell.load(ROOT, args.workload)
    spec = dataclasses.replace(spec, traffic=dict(spec.traffic,
                                                  **json.loads(args.traffic)))
    if args.config:
        config, model = cell.read_config(ROOT, args.config)
        spec = dataclasses.replace(spec, config_name=args.config,
                                   config=config, model=model)
    device = require_chips(spec.chips)[0]
    enable_cache(ROOT)
    if args.mode == "readings":
        readings(spec, [int(s) for s in args.seeds.split(",")], args.seconds,
                 args.control, device, args.fault)
    elif args.mode == "sweep":
        sweep(spec, [float(r) for r in args.rates.split(",")], args.seconds,
              args.seed, device, args.write_rate)
    else:
        record(spec, args.seed, args.seconds, args.trace_seconds, args.out,
               device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
