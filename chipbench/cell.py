"""One cell: its files found by name, and one run of it.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
is a file of its own (``configs/<config>.json``, ``traffic/<traffic>.json``),
each per-layer metric a reader of its own (``metrics/<metric>.py``), and
each configuration its architecture, a module of its own
(``arch/<arch>.py``, by the configuration's key ``"arch"``). A new cell,
metric or architecture is new files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    model: object          # the configuration's architecture module

    @property
    def arch(self) -> dict:
        return self.model.arch_of(self.config)

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(mod_name: str, path: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# A configuration's architecture: a file name under ``arch/``.
ARCH_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")
_ARCHITECTURES: Dict[str, object] = {}   # loaded modules, by file


def architecture(config: dict, path: str):
    """The architecture module a configuration file names with its key
    ``"arch"``: ``arch/<arch>.py`` beside the file's ``configs/`` (``path``:
    the configuration file), loaded once per process. A configuration that
    names none that exists stops the run with a message naming the file."""
    name = config.get("arch")
    home = os.path.dirname(os.path.dirname(os.path.abspath(path)))
    file = os.path.join(home, "arch", f"{name}.py")
    if not isinstance(name, str) or not ARCH_NAME.fullmatch(name) \
            or not os.path.isfile(file):
        raise SystemExit(f"chipbench: the configuration {path} names no "
                         f"architecture module: its \"arch\" is {name!r}, "
                         f"and there is no {file}")
    if file not in _ARCHITECTURES:
        _ARCHITECTURES[file] = _module(
            "chipbench_arch_" + name.replace(".", "_").replace("-", "_"),
            file)
    return _ARCHITECTURES[file]


def read_config(root: str, name: str) -> tuple:
    """The configuration ``chipbench/configs/<name>.json`` under ``root``,
    and its architecture module."""
    path = os.path.join(root, "chipbench", "configs", name + ".json")
    config = _read_json(path)
    return config, architecture(config, path)


def metrics_for(bench: dict, cell: str) -> tuple:
    """The cell's end-to-end and per-layer metrics: those without a
    ``workloads`` list, and those whose list names the cell."""
    def mine(m):
        return "workloads" not in m or cell in m["workloads"]
    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def load(root: str, workload: str) -> CellSpec:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    matches = [w for w in bench["workloads"] if w["name"] == workload]
    if not matches:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json")
    w = matches[0]
    e2e, layers = metrics_for(bench, workload)
    config, model = read_config(root, w["config"])
    return CellSpec(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config,
        traffic=_read_json(os.path.join(root, "chipbench", "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=layers, model=model)


def metric_reader(name: str):
    """The reader module ``metrics/<name>.py``."""
    return _module(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "metrics", name + ".py"))


def _device_info(device) -> dict:
    import jax
    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


class Tracer:
    """Starts the profiler a little into the window and stops it a few
    seconds later, from the serving loop's ticks."""

    def __init__(self, directory: str, start: float, length: float):
        self.directory, self.start, self.length = directory, start, length
        self.t_on = self.t_off = None

    def warm_up(self) -> None:
        """Start and stop the profiler once in set-up: its first start
        loads the profiler and stalls the serving loop for seconds."""
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        jax.profiler.stop_trace()

    def __call__(self, now: float) -> None:
        import jax
        if self.t_on is None and now >= self.start:
            shutil.rmtree(self.directory, ignore_errors=True)
            os.makedirs(self.directory, exist_ok=True)
            jax.profiler.start_trace(self.directory)
            self.t_on = time.perf_counter()
        elif self.t_on is not None and self.t_off is None \
                and now >= self.t_on + self.length:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.t_on is not None and self.t_off is None:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()


def run(spec: CellSpec, *, seed: int, seconds: float, trace: bool, device,
        process_start: float, log: Callable[[str], None], trace_dir: str,
        quantize: Optional[str] = None, trace_seconds: Optional[float] = None,
        keep_trace: Optional[str] = None, stats: Optional[dict] = None) -> dict:
    """One run of the cell; returns the result object (last stdout line).

    ``quantize`` switches on the program's int8 weight path (the control),
    ``trace_seconds`` sets how long the profiler runs, ``keep_trace`` names
    a file to keep the reduced trace in, and ``stats`` (a dict) receives the
    window's readings and the check's counts; these are for
    ``calibrate.py`` and the tests, never for a benchmark run."""
    import numpy as np
    from chipbench import correct, generator, serve, window

    arch, serving, mix = spec.arch, spec.serving, spec.traffic
    if generator.max_total_len(mix) > serving["max_len"]:
        raise ValueError(f"{spec.traffic_name} needs max_len >= "
                         f"{generator.max_total_len(mix)}")
    compiles = serve.CompileCounter()
    t = time.perf_counter()
    probes = correct.probe_ids(seed, arch["vocab_size"])
    server = serve.build_server(spec.model, arch, serving, seed, probes,
                                quantize)
    log(f"[setup] weights built and packed on the device in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    serve.warm_up(server, mix["prompt_len"]["values"])
    log(f"[setup] warm-up of {len(set(mix['prompt_len']['values']))} prompt "
        f"lengths in {time.perf_counter() - t:.3f} s; programs built in "
        f"set-up: {compiles.count}")
    arrivals = generator.arrivals(mix, seed, seconds, arch["vocab_size"])
    tracer = None
    if trace:
        tracer = Tracer(trace_dir, 0.0, trace_seconds or min(3.0, seconds / 2))
        tracer.warm_up()
    setup_end = time.perf_counter()
    setup_s = setup_end - process_start
    built_before = compiles.count
    lead = float(mix["lead_in_s"])
    if tracer is not None:
        tracer.start = setup_end + lead + min(2.0, seconds / 4)
    closed = generator.closed_loop(mix)
    wlog = serve.run_window(
        server, arrivals, lead, seconds,
        outstanding=serving["max_live"] + int(mix["backlog"]) if closed
        else None, on_tick=tracer)
    if closed:
        sent = f"closed loop, {len(wlog.due)} requests submitted"
    else:
        late = np.asarray(wlog.late_s)
        sent = (f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
                f"max {late.max() * 1e3:.3f} ms over {late.size} arrivals")
    if tracer is not None:
        tracer.stop()
    in_window = compiles.count - built_before
    log(f"[window] programs built inside the lead-in, window and drain: "
        f"{in_window}")
    log(f"[window] {sent}; drain {wlog.drained_at - wlog.end:.3f} s; queue at "
        f"mid-window {wlog.queue_mid}, at the window's end {wlog.queue_end}")
    log(f"[window] dispatch {json.dumps(server.engine.dispatch_report)}; "
        f"degraded lowerings {json.dumps(server.engine.health_report())}")
    memory_peak = peak_bytes(device)
    results = server.sched.results
    # The requests submitted: every arrival of an open loop, those the
    # closed loop sent before its window closed.
    attempted = list(wlog.due)
    failed = [r for r in attempted
              if r not in results or results[r].status != "completed"]
    ids = set(attempted)
    events = [(r, s, tt) for r, s, tt, _ in server.clock.events if r in ids]
    m = window.end_to_end(events, wlog.due, wlog.start, wlog.end)
    log(f"[window] {json.dumps(m)}")
    if stats is not None:
        stats.update(m, queue_mid=wlog.queue_mid, queue_end=wlog.queue_end,
                     shed=sum(1 for r in results.values()
                              if r.status == "shed"))
    served = {r: list(results[r].tokens) for r in attempted
              if r in results and results[r].status == "completed"}
    prompts = {a.request_id: a.tokens for a in arrivals
               if a.request_id in ids}
    rows = server.clock.rows
    sample = correct.sample_requests(served, seed,
                                     int(mix["check"]["requests"]), rows)
    probed = [server.clock.probed_logits(r, len(served[r])) for r in sample]
    traced = None
    if trace:
        traced = _traced_metrics(spec, server, wlog, tracer, device, trace_dir,
                                 prompts, log, keep_trace)
    # Free the program's state before the reference runs.
    del server
    gc.collect()
    t = time.perf_counter()
    got = correct.compare(spec.model, arch, seed,
                          [prompts[r] for r in sample],
                          [served[r] for r in sample], probed, probes,
                          serving["max_len"])
    covered = {rows[r] for r in sample if r in rows}
    log(f"[check] reference over {len(sample)} requests from "
        f"{len(covered)} batch rows, "
        f"{got['tokens']} "
        f"served tokens ({got['differ']} not the reference's greedy choice; "
        f"mean gap {got['mean_gap']:.6f}; widest gap "
        f"{got['widest_gap']:.6f}; largest logit error {got['logit_err']:.6f}; "
        f"logit rms error "
        f"{got['logit_rms_err']:.6f}) in {time.perf_counter() - t:.3f} s")
    if stats is not None:
        stats.update(check=got, sample_rows=covered)
    compared = {key[:-len("_limit")]: {"value": got[key[:-len("_limit")]],
                                       "limit": limit}
                for key, limit in spec.config["correct"].items()}
    compared["compiles_in_window"] = {"value": in_window, "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    metrics: Dict[str, dict] = {}
    if trace:
        metrics = traced["metrics"]
    else:
        values = dict(m, setup_s=setup_s)
        for e in spec.end_to_end:
            metrics[e["name"]] = {"value": values[e["name"]], "unit": e["unit"]}
    out = {"correct": bool(ok), "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics,
           "device": dict(_device_info(device), memory_peak_bytes=memory_peak)}
    if trace:
        out["device"].update(busy_s=traced["busy_s"],
                             window_s=traced["window_s"])
        out["breakdown"] = traced["breakdown"]
    out["compared"] = compared
    return out


def _traced_metrics(spec, server, wlog, tracer, device, trace_dir, prompts,
                    log, keep_trace=None) -> dict:
    from chipbench import trace as T
    from chipbench.peaks import peaks
    reduced = T.load(trace_dir)
    prompt_len = {r: len(p) for r, p in prompts.items()}
    if keep_trace:
        T.save_context(keep_trace, reduced, server.clock.calls, wlog.ticks,
                       (tracer.t_on, tracer.t_off), prompt_len, trace_dir)
    ctx = T.Context(trace=reduced, model=spec.model, arch=spec.arch,
                    serving=spec.serving,
                    peaks=peaks(device.device_kind), prompt_len=prompt_len,
                    calls=server.clock.calls, ticks=wlog.ticks,
                    host_window=(tracer.t_on, tracer.t_off))
    metrics = {}
    for m in spec.per_layer:
        value = metric_reader(m["name"]).read(ctx)
        if value is None:
            # Nothing to read (the code it reads is off the path): the
            # metric is left out of the line, never reported as 0.
            log(f"[trace] per-layer metric {m['name']} found nothing to "
                f"read in the traced window of {spec.name}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = T.busy_s(reduced)
    log(f"[trace] busy {busy:.6f} s of {reduced.window_s:.6f} s; "
        f"metrics {json.dumps(metrics)}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"metrics": metrics, "busy_s": busy, "window_s": reduced.window_s,
            "breakdown": T.breakdown(reduced)}
