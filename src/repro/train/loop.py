"""Training loop with Checkmate integration, failure injection, recovery,
and straggler observability.

The loop is the paper's Listing 1 with the Checkmate hook: the train step
already returns the reduce-scattered gradients (the multicast payload), the
loop wraps each iteration in a `repro.core.channel.StepEvent`, and the
checkpointer's ``on_step(event)`` pushes it into a `GradientChannel` toward
the shadow plane — the channel packs the capture into bucket wire layout
once, and the shadow applies the flat buffers with one fused optimizer pass
per bucket (docs/channels.md). Baseline checkpointers ignore grads and do
copy-persist on the *state* instead, which is what stalls them.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.configs.base import ModelConfig
from repro.core.buckets import layout_for_tree
from repro.core.channel import GradientChannel, StepEvent
from repro.core.checkpoint import (BaseCheckpointer, CheckmateCheckpointer,
                                   NoCheckpointer)
from repro.core.recovery import (FailurePlan, checkpoint_from_state,
                                 state_from_checkpoint)
from repro.core.shadow import ShadowCluster
from repro.data.synthetic import SyntheticStream, device_batch
from repro.dist.sharding import ShardingRules
from repro.optim import OptimizerConfig, TrainState
from repro.train.step import build_train_step, make_train_state


class TrainingFailure(RuntimeError):
    pass


@dataclass
class LoopStats:
    steps: int = 0
    losses: list = field(default_factory=list)
    iter_times: list = field(default_factory=list)
    stall_times: list = field(default_factory=list)
    step_ends: list = field(default_factory=list)   # perf_counter per step
    tokens_per_step: int = 0
    failures: int = 0
    recoveries: int = 0
    recovered_at: list = field(default_factory=list)
    straggler_flags: list = field(default_factory=list)
    checkpointer: Optional[BaseCheckpointer] = None

    @property
    def mean_iter(self) -> float:
        return float(np.mean(self.iter_times)) if self.iter_times else 0.0

    @property
    def step_s(self) -> float:
        """Wall seconds per step from the end of the first step to the end
        of the last: capture, checkpointer stalls, host gaps, recoveries
        and replays inside; the first step's compile outside."""
        if len(self.step_ends) < 2:
            return 0.0
        return ((self.step_ends[-1] - self.step_ends[0])
                / (len(self.step_ends) - 1))

    @property
    def tokens_per_s(self) -> float:
        s = self.step_s
        return self.tokens_per_step / s if s else 0.0


def train(cfg: ModelConfig, rules: ShardingRules, *,
          steps: int,
          batch: int,
          seq: int,
          opt: OptimizerConfig = OptimizerConfig(),
          lr_fn: Callable = lambda s: 1e-3,
          checkpointer: Optional[BaseCheckpointer] = None,
          channel: Optional[GradientChannel] = None,
          shadow_nodes: int = 2,
          failure_plan: Optional[FailurePlan] = None,
          seed: int = 0,
          straggler_ema: float = 0.9,
          straggler_factor: float = 2.0,
          state: Optional[TrainState] = None,
          step_hook: Optional[Callable] = None,
          elastic_rules=None) -> tuple[TrainState, LoopStats]:
    """Run ``steps`` iterations; on injected failure, restore from the
    checkpointer (Checkmate: shadow consolidation) and continue.

    ``channel`` is the one-argument spelling of the full paper dataflow:
    ``train(..., channel=PacketizedChannel(topology="rail-optimized"))``
    builds a bootstrapped `ShadowCluster` (``shadow_nodes`` CPU nodes) and a
    `CheckmateCheckpointer` wired through that channel. The built
    checkpointer is exposed as ``stats.checkpointer`` (its ``.shadow`` holds
    the cluster). Mutually exclusive with ``checkpointer``.

    ``step_hook(step, state, stats)`` is called after every completed
    iteration (post checkpointer accounting; replayed iterations after a
    recovery call it again with the replayed step number) — the observation
    point `repro.harness` evaluates its per-step invariants from.

    ``elastic_rules`` is the elastic-restart path (`repro.core.elastic`):
    a `ShardingRules` for the post-failure mesh, or a callable
    ``(failed_step) -> Optional[ShardingRules]`` (None = keep the current
    layout). On recovery the loop re-partitions the restored checkpoint
    onto those rules, recompiles the train step for the new mesh, rebuilds
    the shadow plane + channel against the re-derived bucket layout
    (`CheckmateCheckpointer.reconfigure`, booked as the
    ``elastic-reshard`` stall stage), and resumes. The data stream needs
    no rebuild: ``SyntheticStream.batch_at`` materializes the GLOBAL
    batch and ``device_batch`` re-splits it per the new rules, so global
    batch order is preserved across the shrink by construction.
    """
    mesh = rules.mesh
    failure_plan = failure_plan or FailurePlan()
    stream = SyntheticStream(cfg, batch, seq, seed=seed)
    if state is None:
        state = make_train_state(jax.random.PRNGKey(seed), cfg, rules)
    if channel is not None:
        if checkpointer is not None:
            raise ValueError("pass either checkpointer= or channel=, not both")
        shadow = ShadowCluster(layout_for_tree(state.params), opt,
                               n_nodes=shadow_nodes)
        shadow.bootstrap(state.params, state.mu, state.nu, int(state.step))
        checkpointer = CheckmateCheckpointer(shadow, channel=channel)
    checkpointer = checkpointer or NoCheckpointer()

    step_fn = jax.jit(build_train_step(cfg, mesh, rules, opt, lr_fn),
                      donate_argnums=(0,))
    stats = LoopStats(checkpointer=checkpointer, tokens_per_step=batch * seq)
    ema_iter = None
    step = int(state.step)

    ob = _obs.get()
    span = ob.tracer.span
    host_grads = None
    while step < steps:
        # every span carries the number of the step this iteration completes
        with jax.profiler.StepTraceAnnotation("train", step_num=step + 1):
            with span("data.batch", args={"step": step + 1}):
                batch_np = stream.batch_at(step)
            with span("data.put", args={"step": step + 1}):
                dbatch = device_batch(batch_np, rules)
            t0 = time.perf_counter()
            try:
                if failure_plan.should_fail(step + 1):
                    # fail mid-iteration: device state for this step is lost
                    stats.failures += 1
                    raise TrainingFailure(
                        f"injected failure at step {step + 1}")
                with span("step.compute", args={"step": step + 1}):
                    with span("step.dispatch", args={"step": step + 1}):
                        state, metrics, grads = step_fn(state, dbatch)
                    with span("step.wait", args={"step": step + 1}):
                        jax.block_until_ready(metrics["loss"])
            except TrainingFailure:
                state = None   # lost with the failure; frees HBM for the restore
                with span("recovery.restore", track="recovery",
                          args={"failed_step": step + 1}):
                    restored = checkpointer.restore()
                if restored is None:
                    raise
                nr = (elastic_rules(step + 1) if callable(elastic_rules)
                      else elastic_rules)
                if nr is not None and nr is not rules:
                    # elastic restart: land the consolidated checkpoint on
                    # the reconfigured mesh and rebuild everything the old
                    # layout derived (step function, bucket layout, shadow
                    # plane, channel geometry)
                    rules, mesh = nr, nr.mesh
                    step_fn = jax.jit(
                        build_train_step(cfg, mesh, rules, opt, lr_fn),
                        donate_argnums=(0,))
                    if isinstance(checkpointer, CheckmateCheckpointer):
                        from repro.core.elastic import rebuild_shadow
                        checkpointer.reconfigure(
                            rebuild_shadow(checkpointer.shadow, restored))
                    elastic_rules = None       # the switch fires once
                with span("recovery.place", track="recovery",
                          args={"step": int(restored["step"])}):
                    state = state_from_checkpoint(restored, cfg, rules)
                step = int(restored["step"])
                stats.recoveries += 1
                stats.recovered_at.append(step)
                ob.tracer.instant("recovery.resume", track="recovery",
                                  args={"resumed_step": step})
                ob.metrics.counter("train_recoveries_total",
                                   "Recoveries from injected failures").inc(1)
                continue
            iter_time = time.perf_counter() - t0
            step += 1
            stats.steps += 1
            stats.iter_times.append(iter_time)

            # straggler observability: EMA-based slow-iteration flag
            if ema_iter is None:
                ema_iter = iter_time
            else:
                if iter_time > straggler_factor * ema_iter:
                    stats.straggler_flags.append(step)
                ema_iter = (straggler_ema * ema_iter
                            + (1 - straggler_ema) * iter_time)

            scale = 1.0
            with span("step.readback", args={"step": step}):
                stats.losses.append(float(metrics["loss"]))
                lr = float(metrics["lr"])
                if opt.grad_clip:
                    gn = float(metrics["grad_norm"])
                    scale = min(1.0, opt.grad_clip / (gn + 1e-9))
            if host_grads is not None:
                # the last step's host copy, given back before the next is
                # made (its memory returns here, in the device's gap)
                with span("capture.free", args={"step": step}):
                    host_grads = None
            if isinstance(grads, dict) and getattr(checkpointer,
                                                   "consumes_grads", False):
                # the capture's device->host DMA; the channel packs these
                # host leaves straight into the wire buffer (one further
                # pass). Copy-persist baselines never read grads, so they
                # don't pay it.
                nbytes = sum(g.nbytes for g in grads.values())
                with span("capture.d2h", args={"step": step,
                                               "bytes": nbytes}):
                    host_grads = {k: np.asarray(v) for k, v in grads.items()}
                ob.metrics.counter(
                    "capture_bytes_total",
                    "Gradient bytes copied off the device").inc(nbytes)
            # the device gradients are dead once copied: free them before
            # the next step runs, so two steps' gradients never share HBM
            with span("step.free", args={"step": step}):
                for g in jax.tree.leaves(grads):
                    g.delete()
                del grads
            stall = checkpointer.on_step(StepEvent(
                step=step, grads=host_grads, lr=lr, grad_scale=scale,
                iter_time=iter_time,
                state_fn=lambda: checkpoint_from_state(state)))
            stats.stall_times.append(stall)
            stats.step_ends.append(time.perf_counter())
            ob.metrics.counter("train_steps_total",
                               "Completed iterations").inc(1)
            if step_hook is not None:
                with span("loop.hook", args={"step": step}):
                    step_hook(step, state, stats)

    checkpointer.finalize()
    return state, stats
