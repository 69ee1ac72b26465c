"""What one run hands the per-layer metric readers (`bench/metrics/*.py`).

Times are seconds on the host's ``time.perf_counter`` clock; the
program's spans (`repro.obs`) are moved onto it when the run ends. A
reader returns a number, or None where it finds nothing to read.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


@dataclass
class Span:
    name: str
    track: str
    t0: float
    t1: float
    args: dict

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclass
class Context:
    model: dict                     # the configuration file's keys
    batch: int
    seq: int
    chips: int
    t0: float                       # the measured window
    t1: float
    steps: int                      # steps completed in the window
    spans: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    resume: Optional[dict] = None   # t_fail, t_ready, step
    profile: object = None          # bench.devtrace.Profile of the window
    peaks: Optional[dict] = None    # bench/peaks.json entry of the chip

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and s.t0 >= self.t0 and s.t1 <= self.t1]

    def after(self, name: str, t: float) -> list:
        return [s for s in self.spans if s.name == name and s.t0 >= t]


def reader(name: str):
    """The ``read(ctx)`` of the metric ``name``, from its own file."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
