"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

``compact(path)`` reads the ``.xplane.pb`` a traced run writes and keeps
what the reduction needs, as plain lists: per device plane
(``/device:TPU:<n>``) its ``XLA Ops`` and ``XLA Modules`` lines, and from
the host plane the annotations named in ``HOST_PREFIXES`` (the window's,
the harness's and the program's own spans).  Every event is
``[name, start_ns, duration_ns]`` on the profiler's one clock.

``Reduction`` then gives, inside the window (the host annotation
``chipbench.window``):

* busy seconds: the union of the intervals in which an op ran, per
  device; idle share is 1 minus busy over the window;
* kernel seconds: the summed durations of the module events whose name
  matches a pattern, over all devices;
* the breakdown: the device ops that took most time, and the longest idle
  gaps, each named by the innermost host annotation open over its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
HOST_PREFIXES = ("chipbench.", "wfa.", "serve.", "session.", "wave.")
DEVICE_LINES = ("XLA Ops", "XLA Modules")

Event = List  # [name, start_ns, duration_ns]


def compact(path: str) -> dict:
    """The reduction's input, from an ``.xplane.pb`` file (or the
    directory a profile was written to)."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[0]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [[ev.name, float(ev.start_ns),
                                float(ev.duration_ns)] for ev in ln.events]
                     for ln in plane.lines if ln.name in DEVICE_LINES}
            devices[plane.name] = {k: lines.get(k, []) for k in DEVICE_LINES}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend([ev.name, float(ev.start_ns),
                             float(ev.duration_ns)] for ev in ln.events
                            if ev.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host}


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length, in seconds, of the union of ``(start_ns, end_ns)``."""
    total = 0.0
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total * 1e-9


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that no interval covers."""
    out = []
    t = lo
    for a, b in sorted(intervals):
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def short_op(name: str) -> str:
    """``%wfa_pallas.1 = (...) custom-call(...)`` -> ``wfa_pallas``."""
    head = name.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


class Reduction:
    """The traced window of one run, reduced."""

    def __init__(self, trace: dict):
        self.trace = trace
        wins = [ev for ev in trace["host"] if ev[0] == WINDOW]
        if not wins:
            raise ValueError(f"the trace has no {WINDOW!r} annotation")
        _, start, dur = max(wins, key=lambda ev: ev[2])
        self.lo, self.hi = start, start + dur
        self.devices = sorted(trace["devices"])
        if not self.devices:
            raise ValueError("the trace has no TPU device plane")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _clipped(self, events: Sequence[Event]) -> List[Tuple[float, float]]:
        out = []
        for _, start, dur in events:
            a, b = max(start, self.lo), min(start + dur, self.hi)
            if b > a:
                out.append((a, b))
        return out

    def busy_s(self, device: str) -> float:
        return union_seconds(self._clipped(
            self.trace["devices"][device]["XLA Ops"]))

    @property
    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s

    def module_seconds(self, pattern: str) -> float:
        """Summed in-window durations of the module events matching
        ``pattern``, over all devices."""
        rx = re.compile(pattern)
        total = 0.0
        for d in self.devices:
            evs = [ev for ev in self.trace["devices"][d]["XLA Modules"]
                   if rx.search(ev[0])]
            total += sum(b - a for a, b in self._clipped(evs))
        return total * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """[[op, seconds]] of the device ops that took most time, summed
        over devices."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for ev in self.trace["devices"][d]["XLA Ops"]:
                for a, b in self._clipped([ev]):
                    key = short_op(ev[0])
                    tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
        return [[n, s] for n, s in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def host_open_at(self, t: float) -> str:
        """The innermost host annotation open at ``t`` (the window's own
        when nothing narrower is)."""
        best: Optional[Event] = None
        for ev in self.trace["host"]:
            if ev[1] <= t <= ev[1] + ev[2] and (best is None
                                               or ev[2] < best[2]):
                best = ev
        return best[0] if best is not None else "none"

    def top_gaps(self, k: int = 10) -> List[List]:
        """[[host annotation, seconds]] of the longest idle gaps on any
        device, longest first."""
        found = []
        for d in self.devices:
            ivs = self._clipped(self.trace["devices"][d]["XLA Ops"])
            found.extend(gaps(ivs, self.lo, self.hi))
        found.sort(key=lambda g: g[0] - g[1])
        return [[self.host_open_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in found[:k]]
