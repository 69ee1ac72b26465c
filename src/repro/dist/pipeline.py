"""GPipe pipeline parallelism over a ("stage", "data") mesh.

``pipeline_apply`` runs the classic fill/steady/drain schedule with
``shard_map``: stage weights live sharded over the "stage" axis, microbatch
activations move stage-to-stage with ``ppermute``. With M microbatches and S
stages the schedule takes M + S - 1 ticks, so utilization is M / (M + S - 1)
— ``gpipe_utilization`` is that closed form (the bubble the paper's §2.1
training-stack background assumes).

The schedule computes on every stage every tick (idle ticks produce garbage
that is never routed to the output), trading a few wasted FLOPs for a
branch-free SPMD program — the standard trick for static pipeline schedules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_pp_mesh(n_stages: int, n_data: int):
    """("stage", "data") mesh over the first n_stages * n_data devices."""
    return jax.make_mesh(
        (n_stages, n_data), ("stage", "data"),
        devices=jax.devices()[:n_stages * n_data],
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def pipeline_apply(fn, stage_weights, microbatches, mesh):
    """Apply ``fn(stage_weight, x)`` through all stages, GPipe-scheduled.

    ``stage_weights``: (S, ...) — leading dim sharded over "stage".
    ``microbatches``:  (M, mb, ...) — replicated; stage 0 feeds microbatch
    ``t`` at tick ``t``, the last stage emits microbatch ``t - S + 1``.
    Returns the (M, mb, ...) outputs, replicated (equal to applying the
    stages sequentially).
    """
    S = mesh.shape["stage"]
    M = microbatches.shape[0]

    def run(ws, xs):
        w = ws[0]                                 # this stage's weights
        stage = jax.lax.axis_index("stage")
        fwd = [(i, i + 1) for i in range(S - 1)]
        recv = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros_like(xs)
        for t in range(M + S - 1):
            # stage 0 injects fresh microbatches; later stages consume what
            # the previous stage produced last tick.
            inp = jnp.where(stage == 0, xs[min(t, M - 1)], recv)
            out = fn(w, inp)
            if t >= S - 1:
                outs = outs.at[t - S + 1].set(out)
            if S > 1:
                recv = jax.lax.ppermute(out, "stage", fwd)
        # only the last stage's collected outputs are the real results
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, "stage")

    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_weights, microbatches)


def gpipe_utilization(n_micro: int, n_stages: int) -> float:
    """Fraction of stage-ticks doing useful work: M / (M + S - 1)."""
    return n_micro / (n_micro + n_stages - 1)
