"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is read with ``jax.profiler.ProfileData`` (``*.xplane.pb``) into
plain lists: the operations each device ran (from its ``XLA Ops`` line),
the programs it ran (``XLA Modules``), and the host spans whose names
start with ``bench.`` (the ``jax.profiler.TraceAnnotation`` spans the
harness puts around its own calls).  All times are nanoseconds on the
trace's one clock.

* busy time: the union of a device's operation intervals inside the
  window, averaged over the devices;
* kernel time: the summed durations of the operations of one name;
* program time: the summed durations of the executions of one program;
* idle gaps: the stretches inside the window where a device ran nothing,
  each labelled by the innermost ``bench.*`` span open at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO = re.compile(r"^%?([A-Za-z0-9_.\-]+) = ")


def op_name(name: str) -> str:
    """The HLO instruction name of a device event: TPU traces name an
    operation by its whole HLO text, ``%lowrank_fwd.1 = bf16[...] ...``."""
    m = _HLO.match(name)
    return m.group(1) if m else name


def base_name(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``: the operation's name without
    its numbering."""
    return _SUFFIX.sub("", op_name(name))


_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]  # device plane -> operations
    modules: Dict[str, List[Event]]  # device plane -> program executions
    spans: List[Event]  # host bench.* spans

    # -- reading ---------------------------------------------------------

    @classmethod
    def from_dir(cls, root: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {root}")
        return cls.from_file(paths[-1])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        spans: List[Event] = []
        for plane in pd.planes:
            name = plane.name
            if name.startswith("/device:") and "CPU" not in name:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops[name] = _events(line, op_name)
                    elif line.name == "XLA Modules":
                        modules[name] = _events(line)
            elif name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend(e for e in _events(line)
                                 if e.name.startswith("bench."))
        spans.sort(key=lambda e: e.start)
        return cls(ops, modules, spans)

    # -- windows ---------------------------------------------------------

    def span_window(self, name: str) -> Optional[Interval]:
        """(first start, last end) of the host spans called ``name``."""
        hits = [e for e in self.spans if e.name == name]
        if not hits:
            return None
        return min(e.start for e in hits), max(e.end for e in hits)

    def window(self, name: str = "bench.window") -> Interval:
        w = self.span_window(name)
        if w is not None:
            return w
        every = [e for evs in self.ops.values() for e in evs]
        if not every:
            raise ValueError("trace has no device operations")
        return min(e.start for e in every), max(e.end for e in every)

    # -- device numbers --------------------------------------------------

    def busy_intervals(self, device: str, window: Interval) -> List[Interval]:
        return union([(e.start, e.end) for e in self.ops.get(device, ())],
                     window)

    def busy_s(self, window: Interval) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = sum(_length(self.busy_intervals(d, window)) for d in self.ops)
        return tot / len(self.ops) / 1e9

    def op_time_s(self, window: Interval, match) -> float:
        """Summed duration (seconds, averaged over devices) of operations
        whose name ``match`` accepts, clipped to the window."""
        if not self.ops:
            return 0.0
        tot = 0.0
        for evs in self.ops.values():
            for e in evs:
                if match(e.name):
                    tot += _clip(e, window)
        return tot / len(self.ops) / 1e9

    def op_count(self, window: Interval, match) -> float:
        if not self.ops:
            return 0.0
        n = sum(1 for evs in self.ops.values() for e in evs
                if match(e.name) and _clip(e, window) > 0)
        return n / len(self.ops)

    def module_time_s(self, window: Interval, match) -> float:
        """Summed duration of the executions of programs ``match``
        accepts, averaged over devices."""
        if not self.modules:
            return 0.0
        tot = 0.0
        for evs in self.modules.values():
            for e in evs:
                if match(e.name):
                    tot += _clip(e, window)
        return tot / len(self.modules) / 1e9

    def top_ops(self, window: Interval, n: int = 10) -> List[list]:
        """[[base name, seconds], ...] of the operations that took most
        device time, averaged over devices.  Loops and calls, which only
        hold other operations, are left out."""
        acc: Dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                if base_name(e.name) in _CONTAINERS:
                    continue
                t = _clip(e, window)
                if t > 0:
                    k = base_name(e.name)
                    acc[k] = acc.get(k, 0.0) + t
        k = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def idle_gaps(self, window: Interval, n: int = 10) -> List[list]:
        """[[label, seconds], ...]: idle time of the first device inside
        the window, summed by the innermost ``bench.*`` span open at each
        gap's middle, largest first."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = self.busy_intervals(dev, window)
        gaps, t = [], window[0]
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < window[1]:
            gaps.append((t, window[1]))
        acc: Dict[str, float] = {}
        for a, b in gaps:
            label = self.label_at((a + b) / 2)
            acc[label] = acc.get(label, 0.0) + (b - a)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / 1e9] for name, t in top]

    def label_at(self, t: float) -> str:
        best = None
        for e in self.spans:
            if e.start > t:
                break
            if e.end >= t and e.name != "bench.window" and (
                    best is None or e.start >= best.start):
                best = e
        return best.name if best is not None else "bench.window"


def _events(line, rename=None) -> List[Event]:
    out = []
    for e in line.events:
        name = e.name
        out.append(Event(rename(name) if rename else name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns)))
    return out


def _clip(e: Event, w: Interval) -> float:
    return max(0.0, min(e.end, w[1]) - max(e.start, w[0]))


def union(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to ``window``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, window[0]), min(b, window[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)
