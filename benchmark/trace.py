"""Reduce a profiler trace of the measured window to device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per executed
HLO instruction, named by the instruction's text
(``%segmented_scan_topk.1 = (...) custom-call(...)``), and whose
``XLA Modules`` line holds one event per program run
(``jit__search_grouped(<fingerprint>)``). What is taken from it:

- the window: the host annotation the run puts around its measured
  window (``bench.window``);
- device busy time: the union of the intervals of the operations on
  each device's op line, clipped to the window and averaged over the
  devices; the idle share is one minus busy over the window;
- the device operations that took most time, by program and
  instruction;
- the idle gaps of the first device, each put down to what the host was
  doing at its midpoint: the innermost event covering it on a host
  thread that runs the benchmark's or the program's own annotations;
- a program's device time: the summed durations of its runs on the
  ``XLA Modules`` line, by the jitted function's name (``jit_knn``);
- a kernel's device time: the summed durations of the ops whose
  instruction name, less its numeric suffix, is one of the kernel's
  names (a Pallas kernel's custom call is named after the function
  that calls ``pallas_call``). Only the instruction's own name counts,
  not the operands its text names.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_OWN_PREFIXES = ("bench.", "raft_tpu.", "PjitFunction")
_SKIP_HOST = ("end: ", "ThreadpoolListener")
_HLO = re.compile(r"%?([^\s=]+) = ")
_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_SUFFIX = re.compile(r"(\.\d+)+$")
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str          # HLO instruction name, e.g. ``segmented_scan_topk.1``
    opcode: str        # e.g. ``custom-call``, ``fusion``, ``sort``
    module: str        # the program it ran in, e.g. ``jit__search_grouped``
    start: float       # seconds, on the trace's clock
    end: float

    @property
    def base(self) -> str:
        """The instruction name without its numeric suffix."""
        return _SUFFIX.sub("", self.name)

    @property
    def label(self) -> str:
        return f"{self.module}/{self.name} ({self.opcode})"


def parse_hlo(text: str) -> Tuple[str, str]:
    """``(instruction name, opcode)`` of an op event's HLO text; an event
    that is not HLO text keeps its name and an empty opcode."""
    m = _HLO.match(text)
    if not m:
        return text, ""
    op = _OPCODE.search(text, m.end())
    return m.group(1), op.group(1) if op else ""


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]
    busy_s: float                      # mean over devices
    n_devices: int
    ops: List[Op]                      # first device's ops in the window
    gaps: List[Tuple[float, float, str]]
    modules: List[Tuple[float, float, str]] = dataclasses.field(
        default_factory=list)          # first device's program runs

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> Optional[float]:
        if not self.n_devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time; a control-flow op, whose
        event spans the ops of its body, is left out."""
        total: Dict[str, float] = defaultdict(float)
        for op in self.ops:
            if op.opcode not in _CONTAINERS:
                total[op.label] += op.end - op.start
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        total: Dict[str, float] = defaultdict(float)
        for a, b, what in self.gaps:
            total[what] += b - a
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def kernel_seconds(self, names: Sequence[str]) -> float:
        return sum(op.end - op.start for op in self.ops if op.base in names)

    def module_seconds(self, names: Sequence[str]) -> float:
        return sum(b - a for a, b, name in self.modules if name in names)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def complement(busy: List[Tuple[float, float]], lo: float, hi: float
               ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _device_ops(plane):
    """The plane's ops, each with its program, and its program runs;
    None for a plane with no op line."""
    modules: List[Tuple[float, float, str]] = []
    events = None
    for line in plane.lines:
        if line.name == MODULE_LINE:
            modules = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9,
                              e.name.split("(")[0]) for e in line.events)
        elif line.name == OP_LINE:
            events = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                      for e in line.events]
    if events is None:
        return None
    starts = [m[0] for m in modules]
    ops = []
    for text, a, b in events:
        j = bisect.bisect_right(starts, a) - 1
        module = modules[j][2] if j >= 0 and modules[j][1] >= a else "?"
        ops.append(Op(*parse_hlo(text), module, a, b))
    return ops, modules


def summarize(profile, device_prefix: str = "/device:TPU:") -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_lines = []
    windows = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in line.events]
                windows += [(a, b) for name, a, b in events if name == WINDOW]
                if any(name.startswith(_OWN_PREFIXES)
                       for name, _, _ in events):
                    host_lines.append(
                        [ev for ev in events
                         if ev[2] > ev[1] and not ev[0].startswith(_SKIP_HOST)])
        elif plane.name.startswith(device_prefix):
            found = _device_ops(plane)
            if found is not None:
                devices.append(found)
    if windows:
        lo, hi = max(windows, key=lambda w: w[1] - w[0])
    else:
        spans = [(op.start, op.end) for ops, _ in devices for op in ops]
        lo = min((a for a, _ in spans), default=0.0)
        hi = max((b for _, b in spans), default=0.0)
    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    kept: List[Op] = []
    runs: List[Tuple[float, float, str]] = []
    for i, (ops, modules) in enumerate(devices):
        inside = [op for op in ops if op.end > lo and op.start < hi]
        busy = union((max(op.start, lo), min(op.end, hi)) for op in inside)
        busy_total += sum(b - a for a, b in busy)
        if i == 0:
            first_busy, kept = busy, inside
            runs = [(max(a, lo), min(b, hi), name) for a, b, name in modules
                    if b > lo and a < hi]
    lines = []
    for line in host_lines:
        line.sort(key=lambda ev: ev[1])
        lines.append(([ev[1] for ev in line], line))
    gaps = []
    if devices:
        for a, b in complement(first_busy, lo, hi):
            gaps.append((a, b, _host_activity(lines, 0.5 * (a + b))))
    n = len(devices)
    return Summary(window=(lo, hi), busy_s=busy_total / n if n else 0.0,
                   n_devices=n, ops=kept, gaps=gaps, modules=runs)


def _host_activity(lines, t: float, max_back: int = 512) -> str:
    """Name of the innermost host event covering time ``t``. Events of
    one thread nest, so on each line the covering event that started
    last is the innermost; across threads the shortest one wins."""
    best = None
    for starts, events in lines:
        j = bisect.bisect_right(starts, t) - 1
        for ev in events[max(j - max_back, -1) + 1:j + 1][::-1]:
            if ev[2] >= t:
                if best is None or ev[2] - ev[1] < best[2] - best[1]:
                    best = ev
                break
    return best[0] if best is not None else "untracked"


def read_dir(log_dir: str, device_prefix: str = "/device:TPU:") -> Summary:
    """Summarize the newest trace ``jax.profiler`` wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return summarize(ProfileData.from_file(paths[-1]), device_prefix)
