"""Run one benchmark cell once, as ``BENCHMARK.json`` describes it.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* the cell's ``config`` entry names its file (``bench/configs/``): the
  graph's generator and its parameters, the chunk size, the execution
  policy and the guarantees that ``correct`` holds the run to;
* the configuration's ``generator`` names ``bench/generators/<name>.py``,
  whose ``generate(seed, config)`` makes the graph and whose ``PRODUCES``
  states what the graph is; a configuration that states otherwise is
  refused;
* the cell's ``traffic`` names ``bench/traffic/<traffic>.json``, whose
  ``job`` names the job kind ``bench/jobs/<job>.py`` that runs it;
* a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``,
  or, where that file does not exist, by ``bench/metrics/<stem>.py`` with
  ``<stem>`` the name up to its first dot.

A run: set-up (generate the graph on the device from the seed, copy its
CSR to the host, build the façade session, warm up the cell's job), then
either the timed window (``trace=False``: jobs back to back while less
than ``seconds`` have passed, at least one) or the traced jobs
(``trace=True``), then the check of every job against the numpy
reference.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import NamedTuple, Optional

import numpy as np

from . import trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class RunData(NamedTuple):
    """What a per-layer metric reader may read."""

    jobs: list  # bench.jobs.JobRecord, in order
    component_edges: list  # per job: directed edges searched, or None
    n: int
    m: int
    padded_edges: int  # edges of one chunk store, padding included
    peaks: dict  # the device's row of peaks.json
    trace: Optional[dict]  # trace.reduce_events output, traced runs only


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


@functools.lru_cache(maxsize=None)
def load_module(directory: Path, name: str):
    """The module ``directory/<name>.py``; ``ValueError`` where there is
    none, naming the ones there are."""
    path = directory / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in directory.glob("*.py")
                       if p.stem != "__init__")
        raise ValueError(f"no {path.name} in {directory}; known: {known}")
    modname = (f"bench.{directory.name}."
               + name.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, metrics_dir: Path):
    """The ``read(RunData)`` function of a per-layer metric."""
    if not (metrics_dir / f"{name}.py").is_file():
        name = name.split(".", 1)[0]
    return load_module(metrics_dir, name).read


def generator(config: dict, bench_dir: Path):
    """The configuration's graph generator, once its stated properties
    (``symmetric``, ``self_loops``, ``duplicates``, ...) are the ones the
    generator's ``PRODUCES`` says its graphs have."""
    gen = load_module(bench_dir / "generators", config["generator"])
    for key, value in gen.PRODUCES.items():
        if config.get(key) != value:
            raise ValueError(
                f"configuration {config.get('name')!r} states {key}="
                f"{config.get(key)!r}; generator {config['generator']!r} "
                f"makes {key}={value!r}")
    return gen


def cell_metrics(spec: dict, cell: dict, traffic: dict, trace: bool
                 ) -> list[dict]:
    """The metric entries this cell reports in a run of this kind."""
    name = cell["name"]

    def applies(metric: dict, reported: set) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return metric.get("moves", metric["name"]) in reported

    e2e = {"setup_s", traffic["rate_metric"]}
    if not trace:
        return [m for m in spec["end_to_end"] if m["name"] in e2e]
    return [m for m in spec["per_layer"] if applies(m, e2e)]


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _policy(repro, config: dict, traffic: dict):
    fields = {**config.get("policy", {}), **traffic.get("policy", {})}
    return repro.ExecutionPolicy(**fields) if fields else None


def _traced(jax, g, gen, policy, count: int, log) -> tuple[list, dict]:
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        done = []
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                for i in range(count):
                    t = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.job"):
                        job = gen.run(g, policy, i, traced=True)
                    done.append(job._replace(
                        seconds=time.perf_counter() - t))
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        devices, host = trace_mod.read_xplane(trace_mod.find_xplane(log_dir))
        reduced = trace_mod.reduce_events(devices, host, WINDOW_SPAN)
        log(f"trace: {sum(len(v) for v in devices.values())} device events,"
            f" {len(host)} host events, read in "
            f"{time.perf_counter() - t:.2f} s")
        return done, reduced
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


class Cell(NamedTuple):
    """A cell of ``BENCHMARK.json`` with its configuration and traffic."""

    spec: dict
    entry: dict  # the ``workloads`` entry
    config: dict  # the configuration's file
    traffic: dict  # the traffic's file
    root: Path  # the directory of BENCHMARK.json


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    spec = load_json(spec_path)
    entry, config_entry = find_cell(spec, workload)
    root = spec_path.parent
    traffic = root / "bench" / "traffic" / f"{entry['traffic']}.json"
    if not traffic.is_file():
        raise ValueError(f"cell {workload!r}: no traffic file {traffic}")
    return Cell(spec, entry, load_json(root / config_entry["file"]),
                load_json(traffic), root)


def check_chip(jax, cell: Cell) -> tuple[list, dict]:
    """The cell's devices and their row of peaks; :class:`NoChip` where
    JAX finds no TPU, too few chips, or a device without peaks."""
    devices = jax.devices()
    chips = cell.entry["chips"]
    if jax.default_backend() != "tpu" or len(devices) < chips:
        raise NoChip(f"cell {cell.entry['name']} needs {chips} TPU chip(s);"
                     f" JAX found {len(devices)} {jax.default_backend()} "
                     "device(s)")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:chips], peaks[kind]


class Session(NamedTuple):
    host: object  # repro.graph.csr.Graph
    graph: object  # repro.Graph
    policy: object  # repro.ExecutionPolicy or None
    jobs: object  # the traffic's job kind: bench/jobs/<job>.py's Jobs


def build(cell: Cell, seed: int) -> Session:
    """Generate the graph from ``seed`` (on the device, for the Kronecker
    generator), copy its CSR to the host, and wrap it in a façade session;
    no job has run yet."""
    import repro

    bench_dir = cell.root / "bench"
    gen = generator(cell.config, bench_dir)
    kind = load_module(bench_dir / "jobs", cell.traffic["job"])
    host, unscrambled = gen.generate(seed, cell.config)
    return Session(host,
                   repro.Graph(host, chunk_size=cell.config["chunk_size"]),
                   _policy(repro, cell.config, cell.traffic),
                   kind.Jobs(cell.traffic, host, unscrambled))


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, spec_path: Path = ROOT / "BENCHMARK.json",
             require_chip: bool = True, log=print) -> dict:
    """One run of one cell; returns the result object that ``run.py``
    prints.  Raises :class:`NoChip` before any work without a TPU;
    ``require_chip=False`` (tests) runs on whatever JAX finds."""
    import jax

    cell = load_cell(workload, spec_path)
    if require_chip:
        devices, peaks = check_chip(jax, cell)
    else:
        devices, peaks = jax.devices()[:1], {"hbm_bytes_per_s": 1.0}

    from .compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    spec, traffic, config, root = cell.spec, cell.traffic, cell.config, \
        cell.root

    # ---- set-up -----------------------------------------------------------
    t = time.perf_counter()
    host, g, policy, gen = build(cell, seed)
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    gen.warmup(g, policy)
    t_warm = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"graph {config.get('name')}: n={host.n}, m={host.m} "
        f"directed edges; generated and copied to host in {t_gen:.2f} s; "
        f"view build and warm-up {t_warm:.2f} s; setup_s {setup_s:.2f}")

    # ---- window or traced jobs -------------------------------------------
    reduced = None
    compiles = []
    listener = _compile_listener(jax, compiles)
    if trace:
        done, reduced = _traced(jax, g, gen, policy,
                                int(traffic.get("trace", {}).get("jobs", 1)),
                                log)
        window_s = reduced["window_s"]
    else:
        done = []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            job = gen.run(g, policy, len(done))
            done.append(job._replace(seconds=time.perf_counter() - t))
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    _drop_listener(jax, listener)
    for i, job in enumerate(done):
        log(f"job {i}: {job.seconds:.3f} s, {job.supersteps} supersteps, "
            f"records {job.records}, key {job.key}")
    log(f"window {window_s:.3f} s for {len(done)} job(s), overrun "
        f"{window_s - seconds:.3f} s; compilations in the window: "
        f"{len(compiles)}")

    device = _device_info(devices)
    report = g.memory_report(policy)
    log(f"memory_report: {json.dumps(report)}")
    # Free the program's state before the reference runs.
    done = [j._replace(values=np.asarray(j.values)) for j in done]
    del g
    gc.collect()

    # ---- check ------------------------------------------------------------
    t = time.perf_counter()
    checks, failed = gen.checks(done, traffic["limits"])
    for key, limit in config.get("guarantees", {}).items():
        checks[key] = (report[key], limit)
    log(f"check: {time.perf_counter() - t:.2f} s")
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    comp = [gen.component_edges(j) for j in done]
    if trace:
        data = RunData(done, comp, host.n, host.m,
                       -(-host.m // config["chunk_size"])
                       * config["chunk_size"], peaks, reduced)
        metrics = {}
        for m in cell_metrics(spec, cell.entry, traffic, trace=True):
            value = metric_reader(m["name"], root / "bench" / "metrics")(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        work = sum(gen.work(j) for j in done)
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        rate = traffic["rate_metric"]
        metrics = {rate: {"value": work / window_s, "unit": units[rate]},
                   "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
    result = {"correct": bool(correct), "attempted": len(done),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _compile_listener(jax, sink: list):
    """Record every lowering and backend compilation while the window
    runs: a program that is lowered there was not warmed up."""
    def on_event(event: str, duration: float, **_):
        if event.endswith(("jaxpr_to_mlir_module_duration",
                           "backend_compile_duration")):
            sink.append((event, duration))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return on_event


def _drop_listener(jax, listener) -> None:
    jax.monitoring.unregister_event_duration_listener(listener)
