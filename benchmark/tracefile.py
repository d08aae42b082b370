"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The harness wraps its measured window in a host annotation named
``bench.window``; everything here is clipped to that span.  On each
device plane, busy time is the union of the intervals in which an
operation ran (the ``XLA Ops`` line), a program's time is the summed
duration of its executions (the ``XLA Modules`` line, matched by a
regular expression on the module name), and an idle gap is a hole in
the busy union, named by the shortest host event that covers its
middle: what the host was doing while the device waited.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
#: collective operations, by their HLO op names
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|all_gather|all_reduce|reduce_scatter|collective_permute|all_to_all")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Device:
    name: str
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    """A reduced trace: the window, each device's ops and programs, and
    the host's events, all in ns on the trace's clock."""

    window: tuple
    devices: list
    host: list

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> list:
        """Per device: seconds in which some operation ran."""
        return [_union(_clip(d.ops, self.window)) / 1e9 for d in self.devices]

    def mean_busy_s(self) -> float:
        busy = self.busy_s()
        return sum(busy) / len(busy) if busy else 0.0

    def program_s(self, pattern: str) -> list:
        """Per device: summed seconds of programs whose name matches."""
        rx = re.compile(pattern)
        return [sum(e.end - e.start for e in _clip(d.modules, self.window)
                    if rx.search(e.name)) / 1e9 for d in self.devices]

    def program_calls(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [sum(1 for e in _clip(d.modules, self.window)
                    if rx.search(e.name)) for d in self.devices]

    def op_s(self, pattern) -> list:
        """Per device: summed seconds of operations whose name matches."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return [sum(e.end - e.start for e in _clip(d.ops, self.window)
                    if rx.search(e.name)) / 1e9 for d in self.devices]

    def top_ops(self, k: int = 10) -> list:
        """``[name, seconds]`` of the operations that took most device
        time, summed by name over the devices and divided by their
        number."""
        tot: dict = {}
        for d in self.devices:
            for e in _clip(d.ops, self.window):
                tot[e.name] = tot.get(e.name, 0.0) + (e.end - e.start)
        n = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9 / n] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        """``[host event, seconds]`` for the ``k`` longest holes in the
        first device's busy union inside the window."""
        if not self.devices:
            return []
        busy = _merged(_clip(self.devices[0].ops, self.window))
        gaps, reach = [], self.window[0]
        for lo, hi in busy:
            if lo > reach:
                gaps.append((reach, lo))
            reach = max(reach, hi)
        if self.window[1] > reach:
            gaps.append((reach, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_at((lo + hi) / 2), (hi - lo) / 1e9]
                for lo, hi in gaps[:k]]

    def _host_at(self, t: float) -> str:
        best = None
        for e in self.host:
            if e.start <= t <= e.end and e.name != WINDOW and (
                    best is None or e.end - e.start < best.end - best.start):
                best = e
        return best.name if best is not None else "(no host event)"


def _clip(events, window) -> list:
    lo, hi = window
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def _merged(events) -> list:
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def _union(events) -> float:
    return sum(hi - lo for lo, hi in _merged(events))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce_xplane(path: str, devices: int | None = None) -> Trace:
    """Read one ``.xplane.pb`` with ``jax.profiler.ProfileData``.  Device
    planes are those named ``/device:...``; ``devices`` keeps the first
    that many (the chips the run used)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devs, host = [], []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                target = {_OPS_LINE: ops, _MODULES_LINE: modules}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    target.append(Event(_short(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns))
            if ops or modules:
                devs.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == WINDOW and window is None:
                        window = (ev.start, ev.end)
                    host.append(ev)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    devs.sort(key=lambda d: _device_ordinal(d.name))
    if devices is not None:
        devs = devs[:devices]
    return Trace(window=window, devices=devs, host=host)


def _short(name: str) -> str:
    """An HLO op's name without its text (``%fusion.3 = s32[...] ...``
    becomes ``fusion.3``); other names unchanged."""
    return name.split(" = ", 1)[0].lstrip("%")


def _device_ordinal(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


def idle_pct(trace: Trace) -> float | None:
    """100 x (1 - busy / window), averaged over the devices; None where
    the trace holds no device."""
    busy = trace.busy_s()
    if not busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace.window_s)
