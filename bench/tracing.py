"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is a list of events ``(plane, line, name, start_ns, dur_ns)``
on one clock, in ns from the profile's start. Device planes are
``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one event
per program execution (named ``jit_<fn>(<fingerprint>)``) and the
``XLA Ops`` line one event per operation (named by its HLO text; a
``while`` op spans the ops of its body). The benchmark's host spans,
named ``fl.*``, are kept on the host's real-time clock (``time.time_ns``,
the profiler's own) and put on the trace's by ``host_spans``: the
profiler's host tracer stays off, since on a conv-heavy round the TPU
runtime's threads write millions of host events that slow the round
they trace.

``summarize`` turns the events of one traced window into a
``TraceSummary``: per device the busy time (the union of op
intervals), each op's self time and the program it ran in, the idle
gaps, and the host spans.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU|CPU):\d+$")
SPAN_PREFIX = "fl."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


#: an op's HLO text is kept up to this length (it can run to kilobytes)
NAME_CHARS = 200
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def _short(name: str) -> str:
    """The head of an op's HLO text, with the Mosaic kernel target kept
    where the cut drops it."""
    if len(name) <= NAME_CHARS:
        return name
    tail = f" {KERNEL_TARGET}" if KERNEL_TARGET in name else ""
    return name[:NAME_CHARS] + tail


def read_xspace(path: str) -> Tuple[List[Event], int]:
    """The device planes' events of an ``.xplane.pb`` file, and the
    profile's start on the host's real-time clock (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out, start = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, _short(e.name),
                                 float(e.start_ns), float(e.duration_ns)))
    if start is None:
        raise RuntimeError(f"{path} states no profile_start_time")
    return out, int(start)


def host_spans(spans, start_ns: int) -> List[Event]:
    """``(name, start, end)`` spans in real-time ns as events on the
    trace's clock, whose zero is ``start_ns``."""
    return [Event("/host:CPU", "spans", name, float(a - start_ns),
                  float(b - a)) for name, a, b in spans]


def find_xspace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} trace files under {log_dir}")
    return files[0]


def load_events(path: str) -> List[Event]:
    """Events kept as a JSON list of ``[plane, line, name, start_ns,
    dur_ns]`` rows (the recorded window the tests read)."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


# ------------------------------------------------------------ helpers
def union_ns(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration less that of the events nested
    directly inside it (one line, properly nested intervals)."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e.dur_ns
        stack.append([e, e.dur_ns])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def op_label(hlo: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of an ``XLA Ops`` event name such as
    ``%fusion.52 = f32[1,10] fusion(...), kind=kLoop``; where the text
    was cut before the opcode, the instruction's stem stands for it."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(", hlo)
    if m:
        return m.group(1), m.group(2)
    m = re.match(r"%?([\w.\-]+)\s*=", hlo)
    if m:
        return m.group(1), m.group(1).split(".")[0]
    return hlo[:60], "?"


def module_name(name: str) -> str:
    """``jit_sweep_round(1234)`` -> ``jit_sweep_round``."""
    return name.split("(", 1)[0]


@dataclass
class OpRecord:
    device: str
    module: str
    instruction: str
    opcode: str
    hlo: str
    start_ns: float
    dur_ns: float
    self_ns: float


@dataclass
class TraceSummary:
    window: Tuple[float, float]
    devices: List[str]
    busy_ns: Dict[str, float]
    ops: List[OpRecord]
    modules: List[Event]
    spans: List[Event]
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def idle_by_span(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle device time of the first chip summed by the benchmark
        span the host was in, largest first: ``[(span, seconds)]``."""
        acc: Dict[str, float] = {}
        for name, ns in self.idle_gaps:
            acc[name] = acc.get(name, 0.0) + ns
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns * 1e-9) for name, ns in top]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns.values()) / max(len(self.busy_ns), 1)

    def module_ns(self, pattern: str) -> float:
        """Device time of programs whose name matches ``pattern``,
        averaged over the devices."""
        rx = re.compile(pattern)
        tot = sum(m.dur_ns for m in self.modules
                  if rx.search(module_name(m.name)))
        return tot / max(len(self.devices), 1)

    def span_ns(self, name: str) -> float:
        return sum(s.dur_ns for s in self.spans if s.name == name)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Ops by self time summed over the window, averaged over the
        devices: ``[("<program>:<opcode> <instruction>", seconds)]``."""
        acc: Dict[str, float] = {}
        for o in self.ops:
            key = f"{o.module}:{o.opcode} {o.instruction}"
            acc[key] = acc.get(key, 0.0) + o.self_ns
        k = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns / k * 1e-9) for name, ns in top]


def summarize(events: List[Event]) -> TraceSummary:
    """Reduce the events of one traced window. The window runs from the
    first ``fl.job`` span's start to the last one's end."""
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
    jobs = [s for s in spans if s.name == "fl.job"]
    if not jobs:
        raise RuntimeError("the trace holds no fl.job span")
    lo = min(s.start_ns for s in jobs)
    hi = max(s.end_ns for s in jobs)
    devices = sorted({e.plane for e in events
                      if DEVICE_PLANE.match(e.plane)})
    busy, ops, modules, idle = {}, [], [], []
    for dev in devices:
        dev_ops = [e for e in events if e.plane == dev
                   and e.line == OPS_LINE and e.end_ns > lo
                   and e.start_ns < hi]
        mods = sorted((e for e in events if e.plane == dev
                       and e.line == MODULES_LINE and e.end_ns > lo
                       and e.start_ns < hi), key=lambda e: e.start_ns)
        modules.extend(mods)
        iv = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in dev_ops]
        busy[dev] = union_ns(iv)
        starts = [m.start_ns for m in mods]
        for e, st in self_times(dev_ops):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = (module_name(mods[i].name)
                   if i >= 0 and mods[i].end_ns >= e.end_ns else "?")
            ins, opc = op_label(e.name)
            ops.append(OpRecord(dev, mod, ins, opc, e.name, e.start_ns,
                                e.dur_ns, st))
        if dev == devices[0]:
            for s, e in gaps(iv, lo, hi):
                idle.append((_host_doing(spans, (s + e) / 2), e - s))
    return TraceSummary(window=(lo, hi), devices=devices, busy_ns=busy,
                        ops=ops, modules=modules, spans=spans,
                        idle_gaps=sorted(idle, key=lambda g: -g[1]))


def _host_doing(spans: List[Event], t: float) -> str:
    """The innermost benchmark span around host time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and s.name != "fl.job":
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best is not None else "fl.job"
