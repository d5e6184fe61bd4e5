"""Harness CLI — what ``python -m benchmarks.run`` is a facade over.

    python -m benchmarks.run --smoke --check        # the CI guard
    python -m benchmarks.run --bench quant_gemm     # one bench
    python -m benchmarks.run --list                 # registered specs
    python -m benchmarks.run --smoke --executor manifest --topology tpu-pod

Flow: parse -> arm REPRO_BENCH_SMOKE -> snapshot committed baselines ->
discover bench specs (each ``bench_*`` module registers its own RunSpec) ->
expand the plan -> run it (topology-aware executor routing) -> write the
HarnessReport into the run directory and derive the exit code from it.

``--check`` requires ``--smoke``: the guard compares the ``*.smoke.json``
artifacts the run regenerates; a full run never rewrites them, so a bare
``--check`` would compare the committed baselines against themselves and
report success.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time
from typing import Optional

from repro.compile_cache import enable_compile_cache
from repro.harness import baselines as bl
from repro.harness import registry
from repro.harness.runner import run_plan
from repro.harness.spec import TOPOLOGIES, expand

__all__ = ["main"]

ENV_SMOKE = "REPRO_BENCH_SMOKE"


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="Declarative benchmark/launch harness (repro.harness)")
    p.add_argument("--smoke", action="store_true",
                   help="quick CI tier: smoke-registered benches on "
                        "shrunken sizes (sets REPRO_BENCH_SMOKE=1)")
    p.add_argument("--check", action="store_true",
                   help="regression guard: compare fresh smoke speedups "
                        "against the committed per-topology baselines")
    p.add_argument("--bench", action="append", default=None,
                   metavar="NAME", help="run only the named bench(es)")
    p.add_argument("--run-dir", default=None,
                   help="run directory for the report, per-job logs, "
                        "collected artifacts and manifests "
                        "(default: results/harness/<run-id>)")
    p.add_argument("--executor", choices=("auto", "local", "manifest"),
                   default="auto",
                   help="force an executor instead of topology-aware "
                        "routing (auto: local topologies run in-process, "
                        "multi-host topologies emit manifests)")
    p.add_argument("--topology", choices=sorted(TOPOLOGIES), default=None,
                   help="override every spec's topologies with one named "
                        "topology")
    p.add_argument("--list", action="store_true", dest="list_specs",
                   help="list registered bench specs and exit")
    return p


def main(argv=None, *, package: str = "benchmarks",
         root: Optional[pathlib.Path] = None) -> int:
    args = _parser().parse_args(sys.argv[1:] if argv is None else argv)
    if args.check and not args.smoke:
        print("--check requires --smoke (the guard compares the smoke "
              "artifacts the run regenerates)", file=sys.stderr)
        return 2
    root = pathlib.Path(root) if root is not None \
        else pathlib.Path.cwd()

    if args.smoke:
        os.environ[ENV_SMOKE] = "1"
    # Snapshot the committed baselines BEFORE any bench overwrites them —
    # both the guard and the topology-preserving artifact merge need the
    # pre-run state.
    committed = bl.snapshot_baselines(root) if args.smoke else {}

    specs = registry.discover(package)
    if args.list_specs:
        for spec in specs:
            topos = ",".join(t.key for t in spec.topologies)
            print(f"{spec.bench}  smoke={spec.smoke}  "
                  f"artifact={spec.artifact or '-'}  topologies={topos}")
        return 0

    try:
        plan = expand(specs, smoke=args.smoke, benches=args.bench,
                      topology=(TOPOLOGIES[args.topology]
                                if args.topology else None))
    except KeyError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    if not plan.jobs:
        print("error: plan expanded to zero jobs", file=sys.stderr)
        return 2

    run_id = time.strftime("run-%Y%m%dT%H%M%S")
    run_dir = (pathlib.Path(args.run_dir) if args.run_dir
               else root / "results" / "harness" / run_id)

    enable_compile_cache()
    report = run_plan(
        plan, root=root, run_dir=run_dir, run_id=run_id, check=args.check,
        committed_baselines=committed,
        executor=None if args.executor == "auto" else args.executor)

    for row in report.regressions:
        if row["status"] == "ok":
            print(f"# guard ok {row['artifact']} [{row['topology']}] "
                  f"{row['row']} {row['field']}: {row['fresh']:.2f} "
                  f"(baseline {row['baseline']:.2f})")
        else:
            desc = row.get("detail") or (
                f"{row['fresh']:.2f} < baseline {row['baseline']:.2f} / "
                f"{report.tolerance}" if "fresh" in row else "")
            loc = " ".join(p for p in (row.get("row"), row.get("field"))
                           if p)
            print(f"REGRESSION {row['artifact']} [{row['topology']}] "
                  f"{loc} {row['status']}: {desc}", file=sys.stderr)
    c = report.counters
    print(f"# harness {report.run_id}: {c['completed']} completed, "
          f"{c['failed']} failed, {c['emitted']} emitted, "
          f"{c['retries']} retries, "
          f"{c['regression_failures']} regression failures")
    print(f"# report: {run_dir / 'harness_report.json'}")
    return report.exit_code


if __name__ == "__main__":
    sys.exit(main())
