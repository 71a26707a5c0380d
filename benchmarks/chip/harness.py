"""The benchmark harness: finds a cell's files by name and runs it once.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives it:

    configs/<config>.json     the configuration as it is run
    configs/<config>.py       its plain reference
    traffic/<traffic>.json    the traffic mix: parameters only, and which
                              `drivers/` module serves it
    drivers/<driver>.py       `run(run, devices) -> dict`: set-up, window, check
    metrics/<metric>.py       `read(facts) -> number or None`

A cell is added by adding such files and entries; nothing here names a
cell, a configuration or a metric.

One run: guard for the accelerator, set up, warm up, measure for
`--seconds`; with `--trace 1`, serve a second window of `--seconds` under
the profiler; read the peak device memory, check the answers against the
reference, and print the contract's result as the last line of standard
output, with every number compared beside its limit (also as the last
lines of standard error).
"""
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
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux `/proc`), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()

#: what the profiler records in a traced window: the device's operations
#: and programs; nothing of the host, whose tracer slows the served loop
PROFILE = dict(host_tracer_level=0, python_tracer_level=0)


# ------------------------------------------------------------------ lookup
def load_benchmark(checkout: Path = CHECKOUT) -> dict:
    return json.loads((Path(checkout) / "BENCHMARK.json").read_text())


_MODULES: Dict[Path, Any] = {}


def load_module(path: Path):
    """Import a file of this benchmark by path, once per process."""
    path = Path(path).resolve()
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "chipbench_%d_%s" % (len(_MODULES), path.stem.replace(".", "_"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def _by_name(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclass
class Cell:
    workload: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    def reference(self):
        return load_module(self.root / "configs" / f"{self.entry['config']}.py")

    def driver(self):
        return load_module(self.root / "drivers" / f"{self.traffic['driver']}.py")

    def reader(self, metric: str):
        return load_module(self.root / "metrics" / f"{metric}.py")


def find_cell(bench: dict, workload: str, root: Path = HERE) -> Cell:
    """The cell named `workload`, with its configuration and traffic files."""
    entry = _by_name(bench["workloads"], workload, "workload")
    root = Path(root)
    config = json.loads((root / "configs" / f"{entry['config']}.json").read_text())
    traffic = json.loads((root / "traffic" / f"{entry['traffic']}.json").read_text())
    return Cell(
        workload=workload, entry=entry, config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        root=root,
    )


def peaks_for(kind: str, root: Path = HERE) -> dict:
    table = json.loads((Path(root) / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; add its "
                       "published peaks with their source")
    return table[kind]


# -------------------------------------------------------------- counters
class CompileCounter:
    """Counts the programs a process obtains: XLA compilations and loads
    from the persistent cache, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.cache_hits, self.compile_s

    def since(self, snap) -> dict:
        c, h, s = snap
        return dict(compilations=self.compiles - c,
                    cache_hits=self.cache_hits - h,
                    compile_s=self.compile_s - s)


# ----------------------------------------------------------------- a run
@dataclass
class Run:
    """What a driver is given, and what it hands back through `window()`."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    counter: CompileCounter
    #: test hooks by name; a benchmark run has none
    hooks: Dict[str, Callable] = field(default_factory=dict)
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    window_programs: Optional[dict] = None
    setup_programs: Optional[int] = None
    trace_dir: Optional[str] = None
    #: the traced window's bounds, in the trace's nanoseconds
    trace_window: Optional[Tuple[int, int]] = None
    memory_peak_bytes: Optional[int] = None

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends here, nothing may compile in it."""
        self.setup_s = process_age_s()
        snap = self.counter.snapshot()
        self.setup_programs = snap[0] + snap[1]
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.window_s = time.perf_counter() - t0
            self.window_programs = self.counter.since(snap)

    @contextlib.contextmanager
    def traced_window(self):
        """With `trace`, a second window after the measured one, recorded by
        the profiler: the device's metrics are read from it, and those taken
        on the host's clock from the measured window, which the profiler
        does not slow. Its bounds are taken on the host's realtime clock,
        which the trace's times count from the session's start on."""
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        for k, v in PROFILE.items():
            setattr(opts, k, v)
        before = time.time_ns()
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        zero = (before + time.time_ns()) // 2  # the session starts in between
        w0 = time.time_ns()
        try:
            yield
        finally:
            w1 = time.time_ns()
            jax.profiler.stop_trace()
            self.trace_window = (w0 - zero, w1 - zero)

    def read_memory_peak(self, devices) -> int:
        peaks = []
        for d in devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def guard(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                            "this benchmark measures nothing elsewhere")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache(jax) -> str:
    """JAX's persistent compilation cache, where the program keeps it
    (`JAX_COMPILATION_CACHE_DIR`, else a fixed directory in the checkout),
    caching every program however fast it compiled."""
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
             root: Path = HERE, require_accelerator: bool = True,
             hooks: Optional[Dict[str, Callable]] = None, cache: bool = True) -> dict:
    """Run one cell once; return the contract's result (with `checks`).

    `require_accelerator=False`, `hooks` and `cache=False` are for tests:
    they run the rest of a run on the CPU, break the timed path through a
    hook, and leave the process's compilation cache as it was."""
    cell = find_cell(bench, workload, root)
    src = CHECKOUT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    chips = int(cell.entry["chips"])
    devices = guard(jax, chips) if require_accelerator else jax.devices()[:chips]
    if cache:
        enable_cache(jax)
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds), trace=bool(trace),
              counter=CompileCounter(), hooks=dict(hooks or {}))
    out = cell.driver().run(run, devices)
    if run.memory_peak_bytes is None:
        run.read_memory_peak(devices)
    facts = dict(out["facts"], window_s=run.window_s, setup_s=run.setup_s,
                 window_programs=run.window_programs, setup_programs=run.setup_programs)
    device = dict(device_info(devices), memory_peak_bytes=run.memory_peak_bytes)
    result: Dict[str, Any] = {"correct": None, "attempted": out["attempted"],
                              "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        from xplane import reduce_trace

        try:
            red = reduce_trace(run.trace_dir, run.trace_window)
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
        facts["trace"] = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        facts["peaks"] = peaks_for(device["kind"], root) if require_accelerator else None
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(SimpleNamespace(**facts, cell=cell))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        values = dict(out["end_to_end"], setup_s=run.setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    checks = out["checks"]
    result["correct"] = bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def print_result(result: dict, err=sys.stderr, out=sys.stdout) -> None:
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {verdict}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)


def main(args) -> int:
    try:
        result = run_cell(load_benchmark(), args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print_result(result)
    return 0
