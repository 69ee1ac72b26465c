#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload gpt3-xl.ckpt --seed 7 --seconds 51 --trace 0

A cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its limits for ``correct`` are in
``bench/limits/<cell>.json`` and each per-layer metric reads from its own
``bench/metrics/<metric>.py``. Nothing here names a cell.

The run builds the training job from the system's public pieces and drives
`repro.train.loop.train` through its two seams only: ``step_hook`` stamps
each completed step, and a failure plan of the harness's own arms one
failure once the window has closed. Set-up runs the first
``COMPARED_STEPS`` steps (compiling the step and warming every shape the
window uses) and reads the program's numbers from them; the window then
measures for ``--seconds``. With a checkpointer the injected failure is
resumed through the loop's own path (consolidate, place onto the chip, the
next step), and the shadow is compared with the live state at the window's
close and after the resume. Once the program's state is freed, the plain
reference (`bench/reference.py`) runs the same three steps and every number
is held to its limit.

``--trace 0`` prints the end-to-end metrics, ``--trace 1`` the per-layer
ones from a profiler trace of the window. The last line of standard output
is one JSON object; without a TPU, or with fewer chips than the cell asks
for, the run exits nonzero and prints none.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, gen, reference  # noqa: E402
from bench.context import Context, Span, reader  # noqa: E402

# the set-up steps, whose readings are compared with the reference
COMPARED_STEPS = 3
STEP_PROGRAM_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class RunError(RuntimeError):
    """The run cannot go on; the message says why."""


class _Stop(Exception):
    """Raised from the step hook to end the training loop."""


# -- the cell ------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict                 # bench/configs/<config>.json
    traffic: dict               # bench/traffic/<traffic>.json
    limits: dict                # bench/limits/<cell>.json
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, workload)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        model=json.loads((root / configs[w["config"]]["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{workload}.json").read_text()
                          )["numbers"],
        end_to_end=e2e, per_layer=per_layer)


# -- JAX set-up -----------------------------------------------------------------

def use_compile_cache(root: Path = ROOT):
    """JAX's persistent cache at one fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Times at which a program was compiled or read from the cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.times = []
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in STEP_PROGRAM_EVENTS:
            self.times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def chips_present(chips: int):
    """The first ``chips`` TPU devices; RunError if there are fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunError(f"no TPU found (JAX sees {devices[0].platform}); "
                       f"the benchmark measures nothing off the chip")
    if len(devices) < chips:
        raise RunError(f"the cell asks for {chips} chips, "
                       f"{len(devices)} present")
    return devices[:chips]


# -- the program ----------------------------------------------------------------

def model_config(model: dict):
    """The system's ModelConfig as the configuration file states it: every
    key of the file that names a field of ModelConfig is applied."""
    import repro.configs as C
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    over = {k: v for k, v in model.items() if k in fields}
    return dataclasses.replace(C.get(model["name"]), **over)


def plane_gap(ckpt: dict, state, control: bool = False) -> tuple:
    """Worst ``max |shadow - live| / max |live|`` over every leaf of params,
    mu and nu, computed on the chip; (inf, "step") if the steps differ.
    ``control`` rounds the shadow's leaves to bfloat16 first, on the host:
    on the chip XLA may drop a round trip through bfloat16 (it allows
    excess precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if int(ckpt["step"]) != int(state.step):
        return math.inf, "step"
    worst, where = 0.0, ""
    for part in ("params", "mu", "nu"):
        live = getattr(state, part)
        for name in sorted(live):
            b = live[name]
            host = np.asarray(ckpt[part][name])
            if control:
                host = host.astype(jnp.bfloat16).astype(host.dtype)
            a = jax.device_put(host, b.sharding)
            d, m = (float(x) for x in _gap(a, b))
            rel = d / m if m else d
            if not math.isfinite(rel):
                rel = math.inf
            if rel >= worst:
                worst, where = rel, f"{part}/{name}"
            del a, host
    return worst, where


def _gap_impl(a, b):
    import jax.numpy as jnp
    return jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))


_GAP = []


def _gap(a, b):
    if not _GAP:
        import jax
        _GAP.append(jax.jit(_gap_impl))
    return _GAP[0](a, b)


class HostLog:
    """What the host did in each step of the window: the process's CPU
    seconds in user and in system mode (every thread), Python's GC pauses,
    and how late a timer thread woke at worst. A step that is slow with
    little CPU and no GC waited; a timer late by as much says the whole
    process stood still, not the trainer's thread alone. Read only to name
    a slow step on standard error."""

    TICK_S = 0.05

    def __init__(self):
        self.rows = []          # (t, user, system, timer lateness since last)
        self.pauses = []        # (t0, t1) of each GC collection
        self._gc_t0 = None
        self._late = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True)

    def start(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        self.mark()

    def stop(self):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _tick(self):
        last = time.perf_counter()
        while not self._stop.wait(self.TICK_S):
            now = time.perf_counter()
            self._late = max(self._late, now - last - self.TICK_S)
            last = now

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.pauses.append((self._gc_t0, time.perf_counter()))
            self._gc_t0 = None

    def mark(self):
        r = resource.getrusage(resource.RUSAGE_SELF)
        late, self._late = self._late, 0.0
        self.rows.append((time.perf_counter(), r.ru_utime, r.ru_stime, late))

    def _step(self, a, b) -> str:
        gc_s = sum(min(e, b[0]) - max(s, a[0]) for s, e in self.pauses
                   if e > a[0] and s < b[0])
        return (f"{b[0] - a[0]:.3f} s: CPU user {b[1] - a[1]:.3f} s system "
                f"{b[2] - a[2]:.3f} s, GC {gc_s:.3f} s, timer late by "
                f"{b[3]:.3f} s")

    def summary(self) -> str:
        steps = sorted(zip(self.rows, self.rows[1:]),
                       key=lambda ab: ab[1][0] - ab[0][0])
        if not steps:
            return "host: no step in the window"
        return (f"host: median step {self._step(*steps[len(steps) // 2])}; "
                f"slowest step {self._step(*steps[-1])}")


class Window:
    """The loop's seams: ``hook`` is its step_hook and the object itself its
    failure plan (``should_fail``)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 shadow, trace_dir=None, plane_control=False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.trace_dir = trace, trace_dir
        self.shadow = shadow
        self.plane_control = plane_control
        self.idx = reference.sample_index(cell.model, seed)
        self.b1 = float(cell.traffic["optimizer"]["b1"])
        self.prog = {}              # the program's readings of steps 1..3
        self.t0 = self.t1 = None
        self.steps = 0
        self.lag0 = 0.0
        self.lag_wait = None
        self.fail_at = None
        self.t_fail = None
        self.resumed = None         # (step, state) after the resume
        self.checks = {}            # number -> value, for the plane
        self.controls = {}
        self.host = HostLog()
        self._annotation = None

    # failure plan
    def should_fail(self, step: int) -> bool:
        if step == self.fail_at and self.t_fail is None:
            self.t_fail = time.perf_counter()
            return True
        return False

    # step hook
    def hook(self, step, state, stats):
        t = time.perf_counter()
        if self.t_fail is not None:
            self.resumed = (step, state)
            raise _Stop
        if self.t0 is None:
            self._read_warm(step, state, stats)
            if step >= COMPARED_STEPS:
                self._open_window()
            return
        self.steps += 1
        self.host.mark()
        if t - self.t0 < self.seconds:
            return
        self.t1 = t
        self._close_window()
        if self.shadow is None:
            raise _Stop
        self._plane("shadow_gap", state)
        self.fail_at = step + 1

    def _read_warm(self, step, state, stats):
        if step == 1:
            self.prog["grad"] = reference.leaf_readings(
                state.mu, self.idx, scale=1.0 / (1.0 - self.b1))
        if step == COMPARED_STEPS:
            self.prog["loss"] = list(stats.losses[:COMPARED_STEPS])
            self.prog["change"] = reference.change_readings(
                state.params, self.cell.model, self.seed, self.idx)

    def _open_window(self):
        import jax
        from repro import obs
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        if self.shadow is not None:
            self.lag0 = self.shadow.lag_wait_s_total
        self.host.start()
        # the instant maps the program's span clock onto t0: nothing between
        self.t0 = time.perf_counter()
        obs.get().tracer.instant("bench.window")

    def _close_window(self):
        import jax
        self.host.stop()
        if self.shadow is not None:
            self.lag_wait = self.shadow.lag_wait_s_total - self.lag0
        if self.trace:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def _plane(self, name, state):
        ckpt = self.shadow.consolidate()
        self.checks[name] = plane_gap(ckpt, state)[0]
        if self.plane_control:
            self.controls[name] = plane_gap(ckpt, state, control=True)[0]
        del ckpt

    def finish_resume(self):
        """After the loop ended: the shadow against the state the resume
        produced (the checkpoint placed on the chip, then one step)."""
        if self.resumed is None:
            return
        step, state = self.resumed
        self._plane("restore_gap", state)
        self.resumed = (step, None)


def program_spans(ob, t_anchor: float) -> list:
    """The program's spans on the perf_counter clock; ``t_anchor`` is when
    the ``bench.window`` instant was emitted."""
    events = ob.tracer.events()
    anchor = [e for e in events if e["name"] == "bench.window"]
    if not anchor:
        return []
    off = t_anchor - anchor[0]["ts"] * 1e-6
    return [Span(e["name"], str(e["tid"]), e["ts"] * 1e-6 + off,
                 (e["ts"] + e["dur"]) * 1e-6 + off, e.get("args", {}))
            for e in events if e.get("ph") == "X"]


def drive(cell: Cell, seed: int, seconds: float, trace: bool = False,
          trace_dir=None, plane_control: bool = False,
          t_process: float = T_PROCESS) -> dict:
    """Build the job, run set-up, the window and (with a checkpointer) the
    resume. Returns what happened; the program's state is freed."""
    import jax
    from repro import obs
    from repro.core.buckets import layout_for_tree
    from repro.core.channel import InProcessChannel
    from repro.core.checkpoint import CheckmateCheckpointer, NoCheckpointer
    from repro.core.shadow import ShadowCluster
    from repro.dist.sharding import ShardingRules, make_local_mesh
    from repro.optim import OptimizerConfig
    from repro.train.loop import train
    from repro.train.step import make_train_state

    tr = cell.traffic
    cfg = model_config(cell.model)
    mesh = make_local_mesh(cell.chips)
    rules = ShardingRules(mesh, fsdp=cfg.fsdp)
    opt = OptimizerConfig(**tr["optimizer"])
    if tr["lr_schedule"] != "constant":
        raise RunError(f"unknown lr schedule {tr['lr_schedule']!r}")
    lr = float(tr["optimizer"]["lr"])
    compiles = CompileLog()

    with obs.enabled_session() as ob:
        state = make_train_state(reference.seed_key(seed), cfg, rules)
        shadow = None
        if tr["checkpointer"] == "checkmate":
            sh = tr["shadow"]
            if sh["channel"] != "inprocess":
                raise RunError(f"unknown channel {sh['channel']!r}")
            shadow = ShadowCluster(layout_for_tree(state.params), opt,
                                   n_nodes=sh["nodes"],
                                   async_mode=sh["async"],
                                   max_lag_steps=sh["max_lag_steps"])
            shadow.bootstrap(state.params, state.mu, state.nu, 0)
            ck = CheckmateCheckpointer(shadow, channel=InProcessChannel())
        elif tr["checkpointer"] == "none":
            ck = NoCheckpointer()
        else:
            raise RunError(f"unknown checkpointer {tr['checkpointer']!r}")
        d = Window(cell, seed, seconds, trace, shadow,
                   trace_dir=trace_dir, plane_control=plane_control)
        # the loop gets the only reference to the initial state: its first
        # step donates it, and the host copy the shadow's bootstrap cached
        # on it must not outlive it
        holder, state = [state], None
        try:
            train(cfg, rules, steps=2**62, batch=tr["batch"], seq=tr["seq"],
                  opt=opt, lr_fn=lambda s: lr, checkpointer=ck,
                  failure_plan=d, seed=seed, state=holder.pop(),
                  step_hook=d.hook)
        except _Stop:
            pass
        if d.t1 is None:
            raise RunError("the training loop ended before the window closed")
        d.finish_resume()
        spans = program_spans(ob, d.t0)
        # the CPU that tests run on keeps no such statistics
        memory = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for dev in mesh.devices.flat)
        if shadow is not None:
            shadow.shutdown()
        d.shadow = None
        del ck, shadow
    gc.collect()

    resume = None
    if d.t_fail is not None and d.resumed is not None:
        step = d.resumed[0]
        ready = [s for s in spans if s.name == "step.compute"
                 and s.t0 >= d.t_fail and s.args.get("step") == step]
        if ready:
            resume = {"t_fail": d.t_fail, "t_ready": ready[0].t1, "step": step}
    return {
        "window": d, "spans": spans, "resume": resume,
        "memory_peak_bytes": int(memory),
        "setup_s": d.t0 - t_process,
        "compiles_in_window": compiles.between(d.t0, d.t1),
    }


# -- the reference and the numbers ----------------------------------------------

def reference_run(cell: Cell, seed: int, mode: str = "f32",
                  half_batch: bool = False) -> dict:
    tr = cell.traffic
    rows = range(tr["batch"] // 2) if half_batch else None
    return reference.run(
        cell.model, tr["optimizer"], seed,
        lambda i: gen.batch_at(tr, cell.model["vocab_size"], seed, i),
        reference.sample_index(cell.model, seed), steps=COMPARED_STEPS,
        mode=mode, rows=rows)


def numbers(cell: Cell, d: Window, ref: dict) -> dict:
    out = compare.training_numbers(d.prog, ref)
    if cell.traffic["checkpointer"] == "checkmate":
        for name in ("shadow_gap", "restore_gap"):
            out[name] = d.checks.get(name, math.inf)
    return out


def judge(limits: dict, values: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}); a number with no limit, or
    a limit with no number, is not correct."""
    checks, ok = {}, set(values) == set(limits)
    for name in sorted(set(values) | set(limits)):
        v = values.get(name, math.inf)
        lim = limits.get(name, {}).get("limit", -math.inf)
        checks[name] = {"value": v if math.isfinite(v) else None,
                        "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks


# -- the metrics -----------------------------------------------------------------

def trace_metrics(cell: Cell, run: dict, profile, device_kind: str) -> dict:
    from bench.peaks import peaks
    d = run["window"]
    ctx = Context(model=cell.model, batch=cell.traffic["batch"],
                  seq=cell.traffic["seq"], chips=cell.chips, t0=d.t0,
                  t1=d.t1, steps=d.steps, spans=run["spans"],
                  counters=({"lag_wait_s": d.lag_wait}
                            if d.lag_wait is not None else {}),
                  resume=run["resume"], profile=profile,
                  peaks=peaks(device_kind))
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out, ctx


def breakdown(profile, ctx: Context) -> dict:
    """The ten device operations that took most time, and the ten longest
    idle gaps, each named by the innermost program span open on the
    trainer's side at its middle."""
    if not profile.chips:
        return {"device_ops": [], "idle_gaps": []}
    ops = {}
    for c in profile.chips:
        for name, ns in c.ops.items():
            ops[name] = ops.get(name, 0.0) + ns * 1e-9 / len(profile.chips)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    trainer = [s for s in ctx.spans if not s.name.startswith("shadow.")]
    gaps = []
    for s, e in sorted(profile.gaps(0), key=lambda g: g[0] - g[1])[:10]:
        mid = ctx.t0 + ((s + e) / 2 - profile.t0_ns) * 1e-9
        open_ = [sp for sp in trainer if sp.t0 <= mid <= sp.t1]
        name = (min(open_, key=lambda sp: sp.dur).name if open_
                else "no program span")
        gaps.append([name, (e - s) * 1e-9])
    return {"device_ops": [[n, v] for n, v in top], "idle_gaps": gaps}


def end_to_end(cell: Cell, run: dict) -> dict:
    d = run["window"]
    values = {
        "tokens_per_s": (d.steps * cell.traffic["batch"] * cell.traffic["seq"]
                         / (d.t1 - d.t0)),
        "setup_s": run["setup_s"],
    }
    if run["resume"] is not None:
        values["resume_s"] = run["resume"]["t_ready"] - run["resume"]["t_fail"]
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


# -- one run --------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """One run of the cell; returns the result object printed last."""
    say = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        run = drive(cell, seed, seconds, trace=trace, trace_dir=tmp)
        d = run["window"]
        say(f"window: {d.steps} steps in {d.t1 - d.t0:.3f} s; "
            f"programs compiled or loaded inside it: "
            f"{run['compiles_in_window']}")
        say(d.host.summary())
        profile = None
        if trace:
            from bench import devtrace
            profile = devtrace.reduce(devtrace.find_xplane(tmp))
    ref = reference_run(cell, seed)
    correct, checks = judge(cell.limits, numbers(cell, d, ref))
    attempted = d.steps + (1 if d.t_fail is not None else 0)
    failed = 0 if d.t_fail is None or run["resume"] is not None else 1
    dev = devices[0]
    result = {"correct": correct and failed == 0, "attempted": attempted,
              "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    if trace:
        metrics, ctx = trace_metrics(cell, run, profile, dev.device_kind)
        device["busy_s"] = profile.busy_s()
        device["window_s"] = profile.window_s
        result.update(metrics=metrics, device=device,
                      breakdown=breakdown(profile, ctx))
    else:
        result.update(metrics=end_to_end(cell, run), device=device)
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not 0 <= args.seed < 2**64:
        sys.exit("bench: --seed must be a whole number in [0, 2**64)")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        sys.exit(f"bench: the system under test is not in this checkout "
                 f"({e})")
    try:
        cell = load_cell(args.workload)
        use_compile_cache()
        devices = chips_present(cell.chips)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    except RunError as e:
        sys.exit(f"bench: {e}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
