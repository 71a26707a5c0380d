"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

The trace holds one plane per device (`/device:TPU:<n>`), whose "XLA Ops"
line has one event per operation that ran, and whose "XLA Modules" line
has one event per program execution, named after the jitted function
(`jit_<name>(<id>)`). Times are nanoseconds from the start of the
profiling session.

`reduce_trace` keeps what lies inside the measured window: the bounds the
harness recorded on the host's clock, or, where none are given, the
benchmark's `bench.window` span on the host plane (`/host:CPU`):

* `busy_s`: the union of the operation intervals, averaged over devices;
* `window_s`: the window's length;
* `op_s` / `op_calls`: device time and count per operation, keyed
  `<program>/<op>` (the `hlo_module` stat and the event name); `op_text`
  adds the event's other text stats (a kernel's long name), which
  `time_of` searches too;
* `module_s` / `module_calls`: the same per program;
* `gaps_by_span`: device idle time, each gap named by the programs
  around it, `<program before> -> <program after>` (`jit_` and the
  program's id left out), so that the host work between two device calls
  can be told apart;
* `breakdown()`: the contract's top-10 lists of both.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"

Interval = Tuple[int, int]  # [start_ns, end_ns)


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    op_s: Dict[str, float] = field(default_factory=dict)
    op_calls: Dict[str, int] = field(default_factory=dict)
    op_text: Dict[str, str] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_calls: Dict[str, int] = field(default_factory=dict)
    gaps_by_span: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_of(self, pattern: str, table: str = "op") -> Tuple[float, int]:
        """(seconds, calls) of every op (or module) whose name matches."""
        rx = re.compile(pattern)
        secs = self.op_s if table == "op" else self.module_s
        calls = self.op_calls if table == "op" else self.module_calls
        names = [n for n in secs if rx.search(self.op_text.get(n, n))]
        return sum(secs[n] for n in names), sum(calls[n] for n in names)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps_by_span.items(), key=lambda kv: -kv[1])[:top]
        # an op's name on the TPU is its whole HLO instruction
        return {"device_ops": [[n[:160], s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _op_key(ev) -> Tuple[str, str]:
    """(`<program>/<op>`, that key and the event's other text stats)."""
    stats = dict(getattr(ev, "stats", ()) or ())
    module = stats.get("hlo_module")
    key = f"{module}/{ev.name}" if isinstance(module, str) else ev.name
    text = " ".join(v for k, v in stats.items() if isinstance(v, str) and k != "hlo_module")
    return key, f"{key} {text}".strip()


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: Interval, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def _gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _program(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _label(modules: List[Tuple[int, int, str]], gaps: List[Interval]) -> List[str]:
    """For each gap, `<program before> -> <program after>`: the last
    program to start before the gap and the first to start after it
    (`start` or `end` at the window's edges)."""
    starts = [m[0] for m in modules]
    out = []
    for s, e in gaps:
        i = bisect.bisect_left(starts, s) - 1
        j = bisect.bisect_left(starts, e)
        before = _program(modules[i][2]) if i >= 0 else "start"
        after = _program(modules[j][2]) if j < len(modules) else "end"
        out.append(f"{before} -> {after}")
    return out


def _window_span(planes) -> Interval:
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n == WINDOW_SPAN:
                        return s, e
    raise ValueError(f"no window given and no {WINDOW_SPAN} span on the host plane")


def reduce_planes(planes, window: Optional[Interval] = None) -> Reduction:
    """`planes`: objects with `.name` and `.lines` (each with `.name` and
    `.events` having `.name`, `.start_ns`, `.duration_ns`), as
    `jax.profiler.ProfileData` gives them; `window`: [start, end) in the
    trace's nanoseconds, else the `bench.window` span's."""
    planes = list(planes)  # ProfileData's planes can be iterated once
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    lo, hi = window if window is not None else _window_span(planes)
    red = Reduction(window_s=(hi - lo) * 1e-9, busy_s=0.0, devices=len(devices))
    op_s = collections.defaultdict(float)
    op_calls = collections.Counter()
    op_text: Dict[str, str] = {}
    mod_s = collections.defaultdict(float)
    mod_calls = collections.Counter()
    gaps = collections.defaultdict(float)
    busy_total = 0.0
    for plane in devices:
        intervals, modules = [], []
        for line in plane.lines:
            if line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                iv = _clip((int(ev.start_ns), int(ev.start_ns + ev.duration_ns)), lo, hi)
                if iv is None:
                    continue
                if line.name == OP_LINE:
                    key, text = _op_key(ev)
                    intervals.append(iv)
                    op_s[key] += (iv[1] - iv[0]) * 1e-9
                    op_calls[key] += 1
                    op_text.setdefault(key, text)
                else:
                    modules.append((iv[0], iv[1], ev.name))
                    mod_s[ev.name] += (iv[1] - iv[0]) * 1e-9
                    mod_calls[ev.name] += 1
        busy = union(intervals)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        idle = _gaps(busy, lo, hi)
        for (s, e), name in zip(idle, _label(sorted(modules), idle)):
            gaps[name] += (e - s) * 1e-9 / len(devices)
    red.busy_s = busy_total / max(len(devices), 1)
    red.op_s, red.op_calls, red.op_text = dict(op_s), dict(op_calls), op_text
    red.module_s, red.module_calls = dict(mod_s), dict(mod_calls)
    red.gaps_by_span = dict(gaps)
    return red


def reduce_trace(trace_dir, window: Optional[Interval] = None) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(find_xplane(trace_dir))).planes, window)
