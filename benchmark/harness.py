"""The general part of a run: find the cell's files by name, look for the
chip, time set-up, drive the runner's window (under the profiler with
``--trace 1``), read the memory peak, decide ``correct`` from the runner's
numbers and the cell's limits, read the metrics through their readers and
print the result line.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A runner or a metric reader, found by file name (names may hold
    dots and dashes, so not by import path)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str, rehearse: bool = False):
    """Everything ``BENCHMARK.json`` and the data files say of one cell."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    entry = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(BENCH_DIR, "traffic",
                                      entry["traffic"] + ".json"))
    if rehearse:
        config = {**config, **traffic.get("rehearsal", {})}
    limits_path = os.path.join(BENCH_DIR, "limits", workload + ".json")
    limits = _read_json(limits_path) if os.path.exists(limits_path) else {}
    return SimpleNamespace(bench=bench, chips=int(entry["chips"]),
                           config=config, traffic=traffic,
                           limits=limits.get("limits", {}))


def metric_entries(bench: dict, workload: str, traced: bool):
    """The metrics this run reports: the cell's end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def look_for_chips(jax, chips: int, rehearse: bool) -> dict:
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearse:
        if (device["platform"] != "cpu"
                or os.environ.get("JAX_PLATFORMS") != "cpu"):
            raise SystemExit("--rehearse 1 is for JAX_PLATFORMS=cpu, pinned "
                             "by the caller")
        if len(devices) < chips:
            raise SystemExit(
                f"the rehearsal of a {chips}-chip cell needs XLA_FLAGS="
                f"--xla_force_host_platform_device_count={chips}")
        device["count"] = chips
        return device
    if device["platform"] != "tpu":
        raise SystemExit(f"no TPU (jax.devices() is {device}): the benchmark "
                         "has no CPU fallback")
    if len(devices) != chips:
        raise SystemExit(f"the cell asks for {chips} chips, jax.devices() "
                         f"has {len(devices)}")
    return device


def load_peaks(kind: str) -> dict:
    peaks = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in peaks or kind.startswith("_"):
        raise SystemExit(f"no published peaks for device kind {kind!r}: add "
                         "it to benchmark/peaks.json with its source")
    return peaks[kind]


def configure_cache(jax, root: str) -> str:
    """The persistent compilation cache, at a fixed path in the checkout
    (the path is part of the key) unless the environment names one."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or os.path.join(root, ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    # small programs too: every run after the first finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(jax, chips: int) -> int:
    """Peak bytes on the fullest chip. The TPU runtime keeps two books:
    ``peak_bytes_in_use`` counts live buffers (arguments, results), and a
    running program's scratch is carved from the same HBM but counted under
    ``peak_bytes_reserved`` alone (a whole-fit program keeps its L-BFGS
    history there), so the peak is their sum."""
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def decide(numbers: dict, limits: dict):
    """-> (correct, {name: [value, limit]}). A number with no limit, or
    one that is not finite, is not correct."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = [value, limit]
        if (limit is None or not isinstance(value, (int, float))
                or not math.isfinite(value) or value > limit):
            ok = False
    return ok and bool(numbers), compared


def traced_window(jax, runner, seconds: float):
    """The window under the JAX profiler. -> (window, xplane path, dir)."""
    trace_dir = tempfile.mkdtemp(prefix="photon_bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = runner.window(seconds)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return window, (found[0] if found else None), trace_dir


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, rehearse: bool, t_start: float) -> int:
    cell = load_cell(root, workload, rehearse)
    import jax

    cache_dir = configure_cache(jax, root)
    t = time.perf_counter()
    device = look_for_chips(jax, cell.chips, rehearse)
    chip_acquire_s = time.perf_counter() - t
    peaks = None if rehearse else load_peaks(device["kind"])

    from benchmark.compile_meter import CompileMeter

    meter = CompileMeter()
    runner_path = os.path.join(BENCH_DIR, "runners",
                               cell.traffic["runner"] + ".py")
    runner = load_module(runner_path).Runner(cell, seed)
    runner.setup()
    # the TPU runtime's own start-up (8-19 s from process to process on one
    # code, PERF.md) is reported beside set-up, not in it
    setup_s = time.perf_counter() - t_start - chip_acquire_s
    setup_compile = meter.snapshot()

    trace_path = trace_dir = None
    if traced:
        window, trace_path, trace_dir = traced_window(jax, runner, seconds)
    else:
        window = runner.window(seconds)
    after = meter.snapshot()
    device["memory_peak_bytes"] = memory_peak(jax, cell.chips)

    summary, trace_cost = None, {}
    if traced:
        try:
            if trace_path is not None and not rehearse:
                from benchmark import trace_reduce

                t0 = time.perf_counter()
                events = trace_reduce.load_events(trace_path)
                t1 = time.perf_counter()
                summary = trace_reduce.reduce_events(events)
                # the harness's own cost, for the next writer: no metric
                trace_cost = {"trace_load_s": t1 - t0,
                              "trace_reduce_s": time.perf_counter() - t1,
                              **trace_reduce.sizes(events)}
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]

    t0 = time.perf_counter()
    runner.release()
    numbers, attempted, failed = runner.check(window)
    numbers["window_compiles"] = after[1] - setup_compile[1]
    limits = {**cell.limits, "window_compiles": 0}
    correct, compared = decide(numbers, limits)
    check_s = time.perf_counter() - t0

    seconds_taken = window["end"] - window["start"]
    run = SimpleNamespace(window=window, seconds=seconds_taken,
                          passes=sum(p["passes"] for p in window["pieces"]),
                          setup_s=setup_s, chips=cell.chips, peaks=peaks,
                          trace=summary, shapes=runner.shapes())
    metrics = {}
    if not rehearse:
        for m in metric_entries(cell.bench, workload, traced):
            reader = load_module(os.path.join(BENCH_DIR, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["top_ops"][:10],
                               "idle_gaps": summary["top_gaps"][:10]}
    if rehearse:
        result["metrics_note"] = "not measured (CPU rehearsal)"
    result["run"] = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "window_s": seconds_taken,
        "pieces": len(window["pieces"]), "work": runner.describe(window),
        "setup_compile_s": setup_compile[0], "setup_compiles": setup_compile[1],
        "setup_cache_hits": setup_compile[2], "cache_dir": cache_dir,
        "check_s": check_s,
        "setup_phases": {"chip_acquire_s": chip_acquire_s, **runner.phases},
        **trace_cost,
    }
    result["compared"] = compared
    for name, (value, limit) in compared.items():
        print(f"compared {name} = {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
