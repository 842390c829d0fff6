"""Run one benchmark cell once, as ``bench/run.py`` is asked to.

The cell is found by name: ``BENCHMARK.json`` names its configuration and
traffic, ``bench/traffic/<traffic>.json`` names the driver
(``bench/drivers/<driver>.py``) that the traffic's parameters feed,
``bench/workloads/<cell>.json`` holds the limit of every number the
correctness check compares, and every metric is read by
``bench/metrics/<metric>.py``.  A run builds the driver and warms up its
shapes (set-up), loops closed-loop calls for ``--seconds`` (the window,
traced with ``--trace 1``), reads the peak device memory, frees the
program's state, checks a sample of the window's answers against the plain
reference, and prints the result line last on standard output.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), every program in it.
    Called before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache" / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything a run of cell ``name`` reads, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    applies = lambda m: "workloads" not in m or name in m["workloads"]
    read = lambda p: json.loads((root / p).read_text())
    return dict(
        cell=cell, config=read(conf["file"]),
        traffic=read(f"bench/traffic/{cell['traffic']}.json"),
        limits=read(f"bench/workloads/{name}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def make_driver(spec: dict, seed: int, **sizes):
    driver = spec["traffic"]["driver"]
    mod = importlib.import_module(f"bench.drivers.{driver}")
    return mod.Driver(spec["config"], spec["traffic"], seed, **sizes)


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    loaded = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a finished window leaves for the metric readers."""

    setup_s: float
    window_s: float
    items: int
    latencies_s: list
    work: dict
    trace: dict
    peaks: dict


def device_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def check_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def window(driver, seconds: float, trace_dir=None) -> tuple:
    """Closed-loop calls until ``seconds`` have passed; the last call runs
    to its end.  Returns ``(t_start, t_end, calls, items, failed,
    latencies)`` on the host clock."""
    import jax
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = f"bench.{driver.span}"
    lat, items, failed, i = [], 0, 0, 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = t1 = time.perf_counter()
        while t1 - t0 < seconds:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                n, bad = driver.call(i)
            t1 = time.perf_counter()
            lat.append(t1 - t)
            items, failed, i = items + n, failed + bad, i + 1
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return t0, t1, i, items, failed, lat


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, t_process,
             devices=None, **sizes) -> dict:
    """One run of a cell; returns the result line's object.  ``devices``
    (from ``check_chips``) is None only where the caller drives the rest of
    a run without a chip (the tests)."""
    import jax
    compiles = []

    def on_event(event, secs, **kw):
        if "backend_compile" in event:
            compiles.append((time.perf_counter(), event))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run_cell(spec, seed, seconds, trace, t_process, devices,
                         compiles, sizes)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run_cell(spec, seed, seconds, trace, t_process, devices, compiles,
              sizes) -> dict:
    import jax
    driver = make_driver(spec, seed, **sizes)
    driver.warmup()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        t0, t1, n_calls, items, failed, lat = window(driver, seconds, trace_dir)
        setup_s = t0 - t_process
        reduced = {}
        if trace:
            from bench import xplane
            reduced = xplane.reduce(trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    in_window = [e for t, e in compiles if t0 <= t <= t1]
    print(f"window: {n_calls} calls, {items} answers, {t1 - t0:.6f} s; "
          f"compiles in the window: {len(in_window)}", file=sys.stderr)
    run = Run(setup_s=setup_s, window_s=t1 - t0, items=items,
              latencies_s=lat, work=driver.work(n_calls), trace=reduced,
              peaks=device_peaks(dev.device_kind) if devices else {})
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    driver.release()
    gc.collect()
    found = driver.check(n_calls, spec["traffic"]["checks"])
    checks = {k: {"value": float(found[k]), "limit": lim}
              for k, lim in spec["limits"].items()}
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace and reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    out = {"correct": correct, "attempted": items, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and reduced:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    info = {k: v for k, v in found.items() if k not in checks}
    if info:
        print("not compared: " + ", ".join(f"{k}={v!r}" for k, v in
                                           info.items()), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    out["checks"] = checks
    return out


def main(args, t_process) -> int:
    spec = load_cell(args.workload)
    try:
        devices = check_chips(spec["cell"]["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), t_process,
                   devices=devices)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0
