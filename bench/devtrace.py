"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

The window is the host annotation the harness opens at the window's start
and closes at its end (`WINDOW`); everything is clipped to it. On each
device plane (``/device:TPU:<n>``) the ``XLA Ops`` line holds one event per
operation executed and the ``XLA Modules`` line one per program run. Busy
time is the union of the operation intervals; an idle gap is an interval of
the window that no operation covers.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control flow: these ops hold others, whose time they would count again
CONTAINERS = ("%while", "%conditional", "%call")
NAME_CHARS = 120


@dataclass
class Chip:
    name: str
    busy: list                      # merged [start_ns, end_ns] intervals
    ops: dict                       # op -> ns inside the window, control
                                    # flow left out
    modules: list                   # [name, start_ns, end_ns] inside it


@dataclass
class Profile:
    t0_ns: float                    # the window on the profiler's clock
    t1_ns: float
    chips: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(sum(e - s for s, e in c.busy) for c in self.chips
                   ) * 1e-9 / len(self.chips)

    def gaps(self, chip: int = 0) -> list:
        """Idle [start_ns, end_ns] intervals of one chip in the window."""
        out, t = [], self.t0_ns
        for s, e in self.chips[chip].busy:
            if s > t:
                out.append([t, s])
            t = max(t, e)
        if self.t1_ns > t:
            out.append([t, self.t1_ns])
        return out


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def reduce(path: str, device_prefix: str = "/device:TPU:") -> Profile:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    t0, t1 = window
    prof = Profile(t0, t1)
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(device_prefix)),
                     key=lambda p: p.name)
    for plane in devices:
        spans, ops, modules = [], {}, []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, t0), min(ev.end_ns, t1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    spans.append((s, e))
                    if not ev.name.startswith(CONTAINERS):
                        name = ev.name[:NAME_CHARS]
                        ops[name] = ops.get(name, 0.0) + (e - s)
                else:
                    modules.append([ev.name, s, e])
        prof.chips.append(Chip(plane.name, merge(spans), ops, modules))
    return prof
