"""End-to-end training driver with Checkmate per-iteration checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 50 --batch 8 --seq 64 --shadow-nodes 2 \
        --checkpointer checkmate --fail-at 20,35

The job trains on a (data=chips, model=1) mesh over the chips this host
has (``--chips``, default all of them). ``--reduced`` swaps in a tiny
same-family config for a CPU; ``--layers N`` keeps every published width
and cuts the depth to N whole layers, which is how a published model is
sized to one chip (``chip_smoke.py`` trains gpt3-xl that way). Production
256/512-chip meshes are lowered by `repro.launch.dryrun`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path

# one fixed directory in the checkout: the cache key includes the path
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache():
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads that itself), else in `COMPILE_CACHE_DIR`."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep this many whole layers at published widths "
                         "(0 = the config's depth)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=0,
                    help="gradient-accumulation steps (0 = the config's)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--checkpointer", default="checkmate",
                    choices=["checkmate", "none", "sync", "async",
                             "torch_dcp", "gemini", "checkfreq"])
    ap.add_argument("--freq", type=int, default=1)
    ap.add_argument("--channel", default="inprocess",
                    choices=["inprocess", "packetized"],
                    help="gradient delivery transport for checkmate "
                         "(packetized = buckets -> frames -> fabric)")
    ap.add_argument("--topology", default="rail-optimized",
                    choices=["rail-optimized", "leaf-spine", "single"],
                    help="fabric topology for --channel packetized")
    ap.add_argument("--shadow-nodes", type=int, default=2)
    ap.add_argument("--shadow-async", action="store_true")
    ap.add_argument("--fail-at", default="",
                    help="comma-separated steps to inject failures at")
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression with error feedback")
    ap.add_argument("--chips", type=int, default=0,
                    help="data-parallel width: the first N devices "
                         "(0 = every device present)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(enables the tracing session)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the end-of-run metrics snapshot JSON")
    return ap.parse_args(argv)


@dataclasses.dataclass
class RunResult:
    cfg: object                # the ModelConfig trained, depth cut included
    state: object              # the live TrainState after the last step
    stats: object              # repro.train.loop.LoopStats
    checkpointer: object
    report: dict
    digest: str


def run(args) -> RunResult:
    """Build the job ``args`` describes and train it. The caller owns the
    shadow plane (``result.checkpointer.shadow``) and shuts it down."""
    import jax
    import repro.configs as C
    from repro.core.buckets import layout_for_tree
    from repro.core.channel import (CompressedChannel, InProcessChannel,
                                    PacketizedChannel)
    from repro.core.checkpoint import (AsyncCheckpointer, CheckFreqCheckpointer,
                                       CheckmateCheckpointer,
                                       GeminiLikeCheckpointer, NoCheckpointer,
                                       ShardedAsyncCheckpointer,
                                       SyncCheckpointer)
    from repro.core.recovery import FailurePlan
    from repro.core.shadow import ShadowCluster
    from repro.dist.sharding import ShardingRules, make_local_mesh
    from repro.optim import OptimizerConfig
    from repro.optim.schedules import cosine_schedule
    from repro.train.loop import train
    from repro.train.step import make_train_state
    from repro import obs
    from repro.obs.publish import collect_run, render_digest

    published = C.get(args.arch)
    cfg = published.reduced() if args.reduced else published
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.microbatches:
        cfg = dataclasses.replace(cfg, microbatches=args.microbatches)
    mesh = make_local_mesh(args.chips)
    rules = ShardingRules(mesh, fsdp=cfg.fsdp)
    opt = OptimizerConfig(name=args.optimizer, lr=args.lr)
    lr_fn = cosine_schedule(args.lr, warmup=5, total=args.steps)

    state0 = make_train_state(jax.random.PRNGKey(args.seed), cfg, rules)

    shadow = None
    if args.checkpointer == "checkmate":
        layout = layout_for_tree(state0.params)
        shadow = ShadowCluster(layout, opt, n_nodes=args.shadow_nodes,
                               async_mode=args.shadow_async)
        shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)
        if args.channel == "packetized":
            channel = PacketizedChannel(topology=args.topology,
                                        n_shadow_nodes=args.shadow_nodes)
        else:
            channel = InProcessChannel()
        if args.compress:
            channel = CompressedChannel(channel)
        ck = CheckmateCheckpointer(shadow, channel=channel)
    else:
        ck = {
            "none": NoCheckpointer(),
            "sync": SyncCheckpointer(args.freq),
            "async": AsyncCheckpointer(args.freq),
            "torch_dcp": ShardedAsyncCheckpointer(args.freq),
            "gemini": GeminiLikeCheckpointer(args.freq),
            "checkfreq": CheckFreqCheckpointer(),
        }[args.checkpointer]

    plan = FailurePlan(tuple(int(x) for x in args.fail_at.split(",") if x))
    # --trace-out/--metrics-out turn the run's instrumentation on; the
    # digest below works either way (a fresh registry publishes from the
    # subsystems' native counters at end of run)
    session = (obs.enabled_session() if args.trace_out or args.metrics_out
               else None)
    ob = session.__enter__() if session is not None else None
    t0 = time.time()
    # hand the loop the only reference to the initial state: its first step
    # donates it, and the host copy the shadow bootstrap cached on it must
    # not outlive it
    init, state0 = [state0], None
    try:
        state, stats = train(cfg, rules, steps=args.steps, batch=args.batch,
                             seq=args.seq, opt=opt, lr_fn=lr_fn,
                             checkpointer=ck, failure_plan=plan,
                             seed=args.seed, state=init.pop())
        wall = time.time() - t0
        reg = ob.metrics if ob is not None else obs.MetricsRegistry()
        digest_snap = collect_run(reg, checkpointer=ck)
        if args.trace_out:
            ob.tracer.write(args.trace_out)
        if args.metrics_out:
            reg.write_json(args.metrics_out)
    finally:
        if session is not None:
            session.__exit__(None, None, None)

    report = {
        "arch": cfg.name, "layers": cfg.num_layers,
        "layers_published": published.num_layers,
        "chips": mesh.devices.size, "steps": stats.steps,
        "final_loss": stats.losses[-1] if stats.losses else None,
        "tokens_per_s": round(stats.tokens_per_s, 1),
        "mean_iter_s": round(stats.mean_iter, 4),
        "checkpoints": ck.n_checkpoints,
        "stall_total_s": round(ck.stall_total, 4),
        "failures": stats.failures, "recoveries": stats.recoveries,
        "wall_s": round(wall, 2),
    }
    if shadow is not None:
        report["channel"] = ck.channel.name
        if ck.skipped_steps:
            report["gated_steps"] = ck.skipped_steps
        s = shadow.stats()
        report["shadow"] = {
            "nodes": args.shadow_nodes, "lag": s.lag,
            "mean_apply_s": round(s.mean_apply_s, 4),
            "max_queue_depth": s.max_queue_depth,
        }
    # satellite: one-screen end-of-run digest sourced from the metrics
    # registry (same numbers `python -m repro.obs summary` reports)
    return RunResult(cfg, state, stats, ck, report,
                     render_digest(digest_snap, ck=ck))


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    res = run(args)
    shadow = getattr(res.checkpointer, "shadow", None)
    if shadow is not None:
        shadow.shutdown()
    print(json.dumps(res.report, indent=2))
    print(res.digest)


if __name__ == "__main__":
    main()
