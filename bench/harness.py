"""The harness: find a cell's files by name, time its window, read its
metrics, print its result line.

Nothing here knows a configuration, a traffic mix or a metric. A cell of
``BENCHMARK.json`` names its configuration and its mix; the
configuration's file names its driver (``drivers/<driver>.py``); every
metric the cell reports is read by ``metrics/<metric>.py``. A driver
module has one function::

    make(config, traffic, *, seed, impl, devices) -> driver

and the driver has ``setup()``, ``dispatch()``, ``throttle()``,
``drain()``, ``completed()``, ``work_per_call()``, ``release()`` and
``check()`` (see ``drivers/stencil_sweep.py``). A metric module has
``read(run) -> float | None``: ``None`` leaves the metric out of the
line, as when a trace holds nothing it can read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# JAX's monitoring events for a trace to a jaxpr and a backend compile.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that cannot be made: the process exits non-zero with no
    result line."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    root: Path


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    peaks: dict                 # the device's row of peaks.json
    setup_s: float
    window_s: float             # host clock, first dispatch to all ready
    calls: int                  # engine calls completed in the window
    cell_updates: int           # grid-cell updates completed, all chips
    work: dict                  # per engine call and chip: flops, bytes
    trace: object = None        # trace.Reduced of a --trace 1 run


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise BenchError(f"unknown {what} {name!r}; known: {known}")


def _reports(metric: dict, workload: str, e2e_names: set[str]) -> bool:
    """A per-layer metric is reported in the cells it lists, or else in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in e2e_names


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    spec = load_json(root / "BENCHMARK.json")
    w = _named(spec["workloads"], workload, "workload")
    cfg = _named(spec["configs"], w["config"], "configuration")
    e2e = tuple(m for m in spec["end_to_end"]
                if "workloads" not in m or workload in m["workloads"])
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if _reports(m, workload, names))
    return Cell(name=workload,
                config=load_json(root / cfg["file"]),
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json"),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer,
                root=root)


def driver_module(cell: Cell):
    name = cell.config["driver"]
    return load_module(cell.root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def metric_reader(cell: Cell, name: str):
    return load_module(cell.root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name}")


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(known: {', '.join(sorted(table))})")
    return table[kind]


class CompileCounter:
    """Counts traces to a jaxpr and backend compiles while armed."""

    def __init__(self):
        self.armed = False
        self.traces = 0
        self.compiles = 0

    def install(self):
        import jax

        def listen(event, duration, **_):
            if not self.armed:
                return
            if event == TRACE_EVENT:
                self.traces += 1
            elif event == COMPILE_EVENT:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
        return self


def measure(driver, seconds: float, annotate) -> float:
    """Dispatch until ``seconds`` have passed, then wait for the last
    result. The window runs from the first dispatch until every result
    is ready; the host never waits on the device inside it except to
    hold the driver's number of dispatches in flight."""
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            with annotate("bench.dispatch"):
                driver.dispatch()
            if time.perf_counter() - t0 >= seconds:
                break
            with annotate("bench.throttle"):
                driver.throttle()
        with annotate("bench.drain"):
            driver.drain()
    return time.perf_counter() - t0


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, impl: str = "pallas", require_tpu: bool = True,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Set up, measure and check one run of ``cell``; print and return
    its result line. Raises :class:`BenchError` where no run can be made.
    ``impl`` and ``require_tpu`` exist for the CPU tests, which drive a
    cell at a small size through the Pallas interpreter."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips; JAX "
                         f"found {len(devices)}")
    peaks = peaks_for(dev.device_kind, cell.root) if require_tpu else {}
    used = devices[:cell.chips]
    counter = CompileCounter().install()

    t_driver = time.perf_counter()
    driver = driver_module(cell).make(cell.config, cell.traffic, seed=seed,
                                      impl=impl, devices=used)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.6f} s, of which {t_driver - t_start:.6f} s "
          f"process, JAX and chip start, {setup_s - t_driver + t_start:.6f}"
          f" s the driver's set-up", file=err, flush=True)

    counter.armed = True
    if trace:
        from bench import trace as tr
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                window_s = measure(driver, seconds,
                                   jax.profiler.TraceAnnotation)
            finally:
                jax.profiler.stop_trace()
            counter.armed = False
            reduced = tr.reduce_dir(tdir, kernel=cell.config["kernel"])
    else:
        window_s = measure(driver, seconds,
                           lambda name: contextlib.nullcontext())
        counter.armed = False
        reduced = None

    done = driver.completed()
    mem = memory_peak(used)
    driver.release()
    compared = driver.check()
    from repro import obs
    demotions = obs.metrics.counter_total("robust.demotion")
    compared.append({"name": "demotions", "value": demotions, "limit": 0,
                     "ok": demotions == 0})
    correct = all(c["ok"] for c in compared)

    run = Run(cell=cell, peaks=peaks, setup_s=setup_s, window_s=window_s,
              calls=done["calls"], cell_updates=done["cell_updates"],
              work=driver.work_per_call(), trace=reduced)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = metric_reader(cell, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": correct, "attempted": done["calls"],
            "failed": int(demotions), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        line["breakdown"] = reduced.breakdown()
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in compared}

    print(f"window: {window_s:.6f} s, {done['calls']} engine calls, "
          f"{done['cell_updates']} cell updates; in the window "
          f"{counter.traces} traces and {counter.compiles} compiles",
          file=err, flush=True)
    for c in compared:
        print(f"{c['name']} = {c['value']!r} (limit {c['limit']!r}"
              f"{'' if c['ok'] else ', FAILED'})", file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return line
