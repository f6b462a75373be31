"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file
``bench/traffic/<traffic>.json``, the driver ``bench/drivers/<kind>.py``
that the traffic's ``kind`` names, its limits ``bench/limits/<cell>.json``
and one reader ``bench/metrics/<metric>.py`` per metric. A new cell, mix
or metric is new files and entries, never an edit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (read by `bench.tracing`)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@dataclasses.dataclass
class Outcome:
    """What a driver's window measured."""

    window_s: float
    attempted: int
    failed: int
    completed: int
    results: list                       # one answer per request, or None
    results_failed: int = 0             # answers in `results` not `ok`
    mean_iterations: Optional[float] = None
    step_passes: Optional[float] = None  # real steps x passes, all jobs
    latency_s: Optional[np.ndarray] = None
    queue_wait_s: Optional[np.ndarray] = None
    flush_s: Optional[np.ndarray] = None
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """One run of a cell: its files, its arguments and what it measured;
    the metric readers read it."""

    cell: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    problem: object = None
    driver: object = None
    setup_s: Optional[float] = None
    outcome: Optional[Outcome] = None
    trace_summary: Optional[dict] = None
    peaks: Optional[dict] = None


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def find(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise SystemExit(f"bench: no {what} named {name!r}")


def load_run(cell: str, seed: int, seconds: float, trace: bool) -> Run:
    """The run of the cell named ``cell`` in ``BENCHMARK.json``."""
    workload = find(load_benchmark()["workloads"], cell, "workload")
    return make_run(workload, seed, seconds, trace)


def make_run(workload: dict, seed: int, seconds: float, trace: bool,
             traffic_override: Optional[dict] = None) -> Run:
    """The run of ``workload`` (a cell's entry: ``name``, ``config``,
    ``traffic``), which need not be a cell of ``BENCHMARK.json``."""
    from bench import traffic as traffic_lib

    config_entry = find(load_benchmark()["configs"], workload["config"],
                        "config")
    with open(ROOT / config_entry["file"]) as fh:
        config = json.load(fh)
    traffic = traffic_override or traffic_lib.load(workload["traffic"])
    run = Run(cell=workload["name"], workload=workload, config=config,
              traffic=traffic, seed=seed, seconds=seconds, trace=trace)
    run.driver = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    return run


def require_chips(count: int):
    """The TPU devices, or `NoChip`: the benchmark never falls back to
    another platform."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devices} (platform "
                     f"{devices[0].platform!r}); the benchmark runs only "
                     "on a TPU")
    if len(devices) < count:
        raise NoChip(f"the cell asks for {count} chips; JAX finds "
                     f"{len(devices)}")
    return devices[:count]


class CompileCounter:
    """Counts XLA backend compiles through `jax.monitoring`."""

    def __init__(self):
        import jax

        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def load_peaks(device_kind: str) -> dict:
    with open(BENCH_DIR / "peaks.json") as fh:
        table = json.load(fh)
    if device_kind not in table["peaks"]:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json; known: "
                       f"{sorted(table['peaks'])}")
    return table["peaks"][device_kind]


def read_metric(name: str, run: Run) -> Optional[float]:
    """The metric's reader, ``bench/metrics/<name>.py``: ``read(run)``
    returns a number, or None where it finds nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def metrics_for(run: Run) -> dict:
    bench = load_benchmark()
    group = bench["per_layer"] if run.trace else bench["end_to_end"]
    out = {}
    for m in group:
        if "workloads" in m and run.cell not in m["workloads"]:
            continue
        value = read_metric(m["name"], run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peak_bytes(device) -> int:
    """The device's peak footprint: live buffers (``peak_bytes_in_use``:
    arrays and generated code) plus what the allocator reserved for the
    programs' temporaries (``peak_bytes_reserved``, which matches
    ``memory_analysis()``'s temporaries of the largest program)."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def device_record(devices, summary: Optional[dict]) -> dict:
    peak = max(peak_bytes(d) for d in devices)
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if summary is not None:
        rec["busy_s"] = summary["busy_s"]
        rec["window_s"] = summary["window_s"]
    return rec


def run_cell(run: Run, t_start: float) -> dict:
    """Set up, measure, check, and return the result line's object."""
    import jax

    from bench import check as check_lib
    from bench import program, tracing
    from bench.reference.problem import load_problem
    from repro.launch.compile_cache import enable_compile_cache

    chips = run.workload.get("chips", 1)
    devices = require_chips(chips)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"[bench] {run.cell} seed {run.seed}: device "
          f"{devices[0].device_kind} x{len(devices)}, compile cache "
          f"{enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    run.peaks = load_peaks(devices[0].device_kind)
    run.problem = load_problem(run.config["problem"],
                               np.dtype(run.config["dtype"]))
    state = run.driver.setup(run)
    run.setup_s = time.perf_counter() - t_start
    print(f"[bench] set-up {run.setup_s!r} s, {counter.count} compiles, "
          f"{counter.cache_hits} compile-cache hits; combine choices "
          f"{program.backend_choices()}", flush=True)
    compiles_before, hits_before = counter.count, counter.cache_hits
    with tracing.Tracer(run.trace) as tracer:
        with span(tracing.WINDOW_SPAN):
            run.outcome = run.driver.window(run, state)
    run.trace_summary = tracer.summary
    print(f"[bench] window {run.outcome.window_s!r} s, "
          f"{counter.count - compiles_before} compiles and "
          f"{counter.cache_hits - hits_before} compile-cache hits inside it",
          flush=True)
    device = device_record(devices, run.trace_summary)
    print(f"[bench] device memory: {devices[0].memory_stats()}", flush=True)
    metrics = metrics_for(run)
    sample = run.driver.sample(run, state)
    del state["server"]
    correct, checks = check_lib.check(run, state, sample)
    out = {"correct": correct, "attempted": run.outcome.attempted,
           "failed": run.outcome.failed, "metrics": metrics,
           "device": device}
    if run.trace_summary is not None:
        out["breakdown"] = {"device_ops": run.trace_summary["device_ops"],
                            "idle_gaps": run.trace_summary["idle_gaps"]}
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        bound = (f"<= {c['max']!r}" if "max" in c else f">= {c['min']!r}")
        print(f"check {name} {c['value']!r} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
