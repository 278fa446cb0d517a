"""Everything a run shares whatever its cell: finding the cell, its
configuration, traffic and per-layer metrics by name; refusing to
measure anywhere but on the chips the cell asks for; the measured
window and its trace; and the result line.

A cell ``<config>.<traffic>`` is an entry of ``BENCHMARK.json``. Its
parameters are ``workloads/<cell>.json``, whose ``driver`` names the
module of ``drivers/`` that sets it up and drives the program's entry;
its configuration is ``configs/<config>.json`` with the plain reference
``configs/<config>.py`` beside it; a per-layer metric ``<name>`` is read
by ``metrics/<name>.py``. Adding a cell, a configuration or a metric adds
files and edits none."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO = BENCH_DIR.parents[1]


class Refused(SystemExit):
    """The run cannot measure here; exits non-zero and prints no result."""

    def __init__(self, why: str):
        super().__init__(f"chip benchmark: {why}")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Refused(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = REPO / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no {path.name} at {REPO}")
    return read_json(path)


@dataclass
class Cell:
    """One cell: its BENCHMARK.json entry, workload parameters,
    configuration and reference module."""
    name: str
    entry: dict
    params: dict
    config: dict
    reference: object
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def find_cell(name: str, overrides: dict | None = None) -> Cell:
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise Refused(f"unknown workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    params = read_json(BENCH_DIR / "workloads" / f"{name}.json")
    params.update(overrides or {})
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = read_json(REPO / cfgs[entry["config"]]["file"])
    ref = load_module(BENCH_DIR / "configs" / cfg["reference"],
                      "chipbench_ref_" + cfg["name"].replace("-", "_"))

    def mine(metric):
        ws = metric.get("workloads")
        return name in ws if ws is not None else True

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    per_layer = [m for m in bench["per_layer"] if mine(m)]
    return Cell(name, entry, params, cfg, ref, e2e, per_layer)


def program_path() -> Path:
    """The program under test: ``src/`` of the checkout the benchmark
    lies in. Without it the benchmark has nothing to measure."""
    src = REPO / "src"
    if not (src / "repro" / "train" / "engine.py").is_file():
        raise Refused(f"no program at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    return src


def peaks(kind: str) -> dict:
    table = read_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in peaks.json "
                      f"({sorted(table)}); add its published peaks")
    return table[kind]


def chips_for(cell: Cell, allow_cpu: bool = False):
    """The devices the cell runs on. Refuses a CPU (unless a test asks
    for one), fewer devices than the cell needs, and a device kind with
    no peaks in the table."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not allow_cpu:
        raise Refused(f"no accelerator: JAX found {platform!r}")
    if len(devs) < cell.chips:
        raise Refused(f"{cell.name} needs {cell.chips} chips, JAX found "
                      f"{len(devs)}")
    devs = devs[:cell.chips]
    return devs, (peaks(devs[0].device_kind) if platform == "tpu"
                  else None)


@dataclass
class Run:
    """What a driver reports to the harness."""
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    end_to_end: dict                       # metric name -> value
    stats: dict = field(default_factory=dict)
    numbers: dict = field(default_factory=dict)  # compared numbers
    memory_peak_bytes: int | None = None


class Context:
    """What a driver gets: the cell, the seed and window length, the
    process's start time, and the window and annotation hooks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, peak, t0: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.peak, self.t0 = (trace, devices,
                                                        peak, t0)
        self.trace_dir = None

    @property
    def params(self) -> dict:
        return self.cell.params

    def since_start(self) -> float:
        return time.perf_counter() - self.t0

    def mark(self, what: str) -> None:
        """Log how far set-up has come, to standard error."""
        print(f"set-up: {what} at {self.since_start():.3f} s",
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window: profiled when ``--trace 1``, and always
        bracketed by a ``bench.window`` host annotation."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def memory_peak(self):
        peaks_ = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks_.append(int(stats["peak_bytes_in_use"]))
        return max(peaks_) if peaks_ else None


def compile_cache() -> str:
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another), holding
    every program, however quick to compile: a run's set-up then compiles
    nothing that an earlier run of the cell in this checkout compiled."""
    import jax
    from repro.launch.cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, allow_cpu: bool = False,
             overrides: dict | None = None):
    """Set up, measure and check one cell; returns the result line as a
    dict. ``allow_cpu`` and ``overrides`` serve the tests,
    which drive small cells on the CPU and never print a measurement."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = find_cell(name, overrides)
    program_path()
    if not allow_cpu:
        compile_cache()
    devices, peak = chips_for(cell, allow_cpu=allow_cpu)
    driver = load_module(BENCH_DIR / "drivers" / f"{cell.params['driver']}.py",
                         "chipbench_driver_" + cell.params["driver"])
    ctx = Context(cell, seed, seconds, trace, devices, peak, t0)
    try:
        run = driver.run(ctx)
        result = report(cell, ctx, run)
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return result


def report(cell: Cell, ctx: Context, run: Run) -> dict:
    from chipbench.compare import verdict
    ok, checks = verdict(run.numbers, cell.params["limits"])
    d = ctx.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics, breakdown = {}, None
    if ctx.trace:
        from chipbench.trace import load_view
        tv = load_view(ctx.trace_dir, cell.chips)
        device["busy_s"] = tv.busy_mean_s()
        device["window_s"] = tv.window_s
        for m in cell.per_layer:
            reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" +
                                 m["name"].replace(".", "_"))
            v = reader.read(tv, run, cell, ctx.peak)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        breakdown = {"device_ops": tv.top_ops(10),
                     "idle_gaps": tv.idle_gaps(10)}
    else:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": units[m["name"]]}
    out = {"correct": bool(ok), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device,
           "uncompared": {k: v for k, v in run.numbers.items()
                          if k not in checks}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks            # last: what was compared, and limits
    return out
