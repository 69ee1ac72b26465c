"""Fig 7: can the shadow keep up? Batch-size sweep — iteration time vs
shadow pull+optimizer time, and the min shadow-node count (§6.3).

``--json`` mode benchmarks the flat wire-layout apply (one fused optimizer
pass per bucket, `ShadowCluster(flat=True)`) against the legacy per-leaf
path at the gpt2_1_5b layout and writes ``BENCH_shadow.json`` with
mean/max apply seconds for both. Exits nonzero if the flat path is not
faster — the CI smoke gate for the shadow hot loop.

``--json`` additionally plans and times the bucket-sharded frontier
fleet: `repro.core.costmodel.shadow_plan_for_config` sizes arctic_480b
(metadata only — nothing model-sized allocates) and must come back with a
genuinely sharded fleet (>= 8 nodes) whose per-node resident state (the
peak-RSS proxy) fits the budget; a dimension-scaled timing run then
shards the gpt2 leaf tree across that many simulated shadow nodes and
gates on the sharded critical path (slowest node's per-step apply) beating
the single-node apply — the whole point of sharding the shadow plane.

The json benchmark uses the paper's *per-layer* leaf structure for GPT-2
1.5B (48 layers x 12 tensors + embeddings = 580 leaves, the shape a DDP
bucketer actually sees on the capture side), dimension-scaled to fit a CPU
container, bucketed at the default DDP 25 MB cap. The repo's jax models
scan-stack layer weights into ~12 mega-leaves, which hides exactly the
per-leaf dispatch cost the flat path deletes.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import jax

from benchmarks.common import bench_config, csv_row, smoke_env
from repro.core.buckets import layout_for_tree
from repro.core.channel import InProcessChannel, StepEvent
from repro.core.shadow import ShadowCluster, plan_shadow_nodes
from repro.optim import OptimizerConfig
from repro.train.loop import train
from repro.train.step import make_train_state


def run():
    mesh, rules = smoke_env()
    opt = OptimizerConfig(lr=1e-3)
    for arch in ("gpt2-1.5b", "vit-h-14"):
        cfg = bench_config(arch)
        for batch in (2, 8, 16):
            s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
            layout = layout_for_tree(s0.params)
            shadow = ShadowCluster(layout, opt, n_nodes=1)
            shadow.bootstrap(s0.params, s0.mu, s0.nu, 0)
            from repro.core.checkpoint import CheckmateCheckpointer
            _, stats = train(cfg, rules, steps=5, batch=batch, seq=64,
                             opt=opt, state=s0,
                             checkpointer=CheckmateCheckpointer(shadow))
            st = shadow.stats()
            tree = {k: np.asarray(v) for k, v in s0.params.items()}
            n_min, t_apply = plan_shadow_nodes(layout, opt, stats.step_s,
                                               tree)
            keeps_up = st.mean_apply_s < stats.step_s
            csv_row(f"fig7.{cfg.name}.b{batch}", stats.step_s * 1e6,
                    f"iter={stats.step_s*1e3:.0f}ms "
                    f"opt_step={st.mean_apply_s*1e3:.1f}ms "
                    f"min_nodes={n_min} keeps_up={keeps_up}")


def gpt2_1_5b_leaf_tree(d: int = 128, vocab: int = 6272, pos: int = 128,
                        n_layers: int = 48) -> dict[str, np.ndarray]:
    """GPT-2 1.5B's per-layer leaf structure (the DDP capture-side view),
    dimension-scaled (default ~12.5x down from d=1600) for a CPU host."""
    rng = np.random.default_rng(0)

    def t(shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.02

    tree = {"wte.w": t((vocab, d)), "wpe.w": t((pos, d))}
    for i in range(n_layers):
        pre = f"h{i}."
        tree.update({
            pre + "ln1.w": t((d,)), pre + "ln1.b": t((d,)),
            pre + "attn.qkv.w": t((d, 3 * d)),
            pre + "attn.qkv.b": t((3 * d,)),
            pre + "attn.proj.w": t((d, d)), pre + "attn.proj.b": t((d,)),
            pre + "ln2.w": t((d,)), pre + "ln2.b": t((d,)),
            pre + "mlp.fc.w": t((d, 4 * d)), pre + "mlp.fc.b": t((4 * d,)),
            pre + "mlp.proj.w": t((4 * d, d)), pre + "mlp.proj.b": t((d,)),
        })
    tree.update({"lnf.w": t((d,)), "lnf.b": t((d,))})
    return tree


def _time_paths(layout, params, grad_steps, opt: OptimizerConfig):
    """Per-step apply seconds through the channel->shadow hot path for the
    flat and the legacy cluster, INTERLEAVED step by step so both paths see
    the same machine conditions (shared CPU containers throttle in bursts);
    the first (compile-heavy) apply is excluded."""
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    shadows, chans = {}, {}
    for mode, flat in (("flat", True), ("legacy", False)):
        # window sized to the run so the compile-heavy first apply is
        # still in apply_times when we slice it off below
        shadows[mode] = ShadowCluster(layout, opt, n_nodes=1, flat=flat,
                                      apply_times_maxlen=len(grad_steps) + 1)
        shadows[mode].bootstrap(params, zeros, zeros, 0)
        chans[mode] = InProcessChannel()
        chans[mode].open(layout)
    for step, grads in enumerate(grad_steps, start=1):
        for mode in ("flat", "legacy"):
            chans[mode].send(StepEvent(step=step, grads=grads, lr=1e-3))
            for d in chans[mode].poll():
                shadows[mode].on_delivery(d)
    out = {}
    for mode in ("flat", "legacy"):
        chans[mode].close()
        times = list(shadows[mode].nodes[0].apply_times)[1:]
        out[mode] = {"mean_apply_s": float(np.mean(times)),
                     "max_apply_s": float(np.max(times)),
                     "steps": len(times)}
    return out


def _sharded_entry(params, grad_steps,
                   opt: OptimizerConfig) -> tuple[dict, list[str]]:
    """Plan the arctic_480b shadow fleet (metadata only) and time a
    dimension-scaled stand-in sharded across that many nodes.

    The sharded figure of merit is the CRITICAL PATH: nodes apply their
    partitions concurrently in production, so a step costs the slowest
    node's apply, not the sum. The timing layout is rebucketed at a 1 MB
    cap so every node in the fleet actually owns shards (the stand-in is
    ~12.5x dimension-scaled; arctic's real layout has 13k+ buckets), and
    the single-node baseline runs on the SAME layout so per-bucket
    overheads cancel. Returns the report entry plus gate failures (empty
    == all gates pass)."""
    import repro.configs as C
    from repro.core.costmodel import ShadowBudget, shadow_plan_for_config

    budget = ShadowBudget()
    plan = shadow_plan_for_config(C.get("arctic-480b"), budget=budget)

    layout = layout_for_tree(params, cap_bytes=1 << 20)
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    clusters = {
        "single": ShadowCluster(layout, opt, n_nodes=1,
                                apply_times_maxlen=len(grad_steps) + 1),
        "sharded": ShadowCluster(layout, opt, n_nodes=plan.n_nodes,
                                 apply_times_maxlen=len(grad_steps) + 1),
    }
    chan = InProcessChannel()
    chan.open(layout)
    for c in clusters.values():
        c.bootstrap(params, zeros, zeros, 0)
    for step, grads in enumerate(grad_steps, start=1):
        chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
        for d in chan.poll():
            for c in clusters.values():
                c.on_delivery(d)
    chan.close()
    # slowest owner per step == the distributed fleet's step time; the
    # first (compile-heavy) apply is excluded, empty owners apply in ~0
    per_node = [list(n.apply_times)[1:] for n in clusters["sharded"].nodes
                if n.apply_times]
    n_steps = min(len(t) for t in per_node)
    critical = [max(t[s] for t in per_node) for s in range(n_steps)]
    single_mean_s = float(np.mean(
        list(clusters["single"].nodes[0].apply_times)[1:]))

    entry = {
        "arch": "arctic-480b",
        "plan": {"n_nodes": plan.n_nodes, "ram_bound": plan.ram_bound,
                 "nic_bound": plan.nic_bound, "n_buckets": plan.n_buckets,
                 "grad_bytes": plan.grad_bytes,
                 "state_bytes": plan.state_bytes,
                 "bytes_per_node_max": plan.bytes_per_node_max,
                 "gbps_per_node_max": plan.gbps_per_node_max,
                 "usable_ram_per_node": budget.usable_ram},
        "timing": {"workload": "gpt2-1.5b leaf tree (dim-scaled, "
                               "1 MB buckets)",
                   "n_nodes": plan.n_nodes,
                   "n_timing_buckets": len(layout.buckets),
                   "owners_with_shards": len(per_node),
                   "critical_path_mean_s": float(np.mean(critical)),
                   "critical_path_max_s": float(np.max(critical)),
                   "single_node_mean_s": single_mean_s,
                   "speedup_vs_single": single_mean_s
                   / float(np.mean(critical)),
                   "steps": n_steps},
    }
    fails = []
    if plan.n_nodes < 8:
        fails.append(f"arctic-480b plan is {plan.n_nodes} nodes; the "
                     "frontier fleet must be genuinely sharded (>= 8)")
    if plan.bytes_per_node_max > budget.usable_ram:
        fails.append("per-node peak RSS proxy "
                     f"({plan.bytes_per_node_max / 1e9:.1f} GB) exceeds "
                     f"usable RAM ({budget.usable_ram / 1e9:.1f} GB)")
    if float(np.mean(critical)) >= single_mean_s:
        fails.append("sharded critical path "
                     f"({np.mean(critical) * 1e3:.2f} ms) is not faster "
                     f"than the single-node apply "
                     f"({single_mean_s * 1e3:.2f} ms)")
    return entry, fails


def _overlapped_entry(opt: OptimizerConfig, steps: int = 10,
                      delay_s: float = 0.01,
                      max_lag: int = 3) -> tuple[dict, list[str]]:
    """Overlapped multi-step apply under a throttled applier: the
    bounded-lag cluster (batched K-step catch-up drains) vs the legacy
    unbounded one-delivery-per-wakeup path.

    Both appliers are throttled identically, so the figure of merit is
    backlog shape, not apply speed: the bounded cluster must hold its
    queue at the lag bound and drain in O(K) applies at consolidate, where
    the sequential path backlogs O(steps) and pays for every one of them
    after the last send. Gates on exactly that separation."""
    import time

    def drive(max_lag_steps):
        tree = gpt2_1_5b_leaf_tree(n_layers=4)
        layout = layout_for_tree(tree, cap_bytes=1 << 20)
        shadow = ShadowCluster(layout, opt, n_nodes=2, async_mode=True,
                               max_lag_steps=max_lag_steps)
        for node in shadow.nodes:       # throttle the fused apply itself so
            orig = node._apply          # batched replays pay it per step
            node._apply = (lambda *a, _o=orig:
                           (time.sleep(delay_s), _o(*a))[1])
        zeros = {k: np.zeros_like(v) for k, v in tree.items()}
        shadow.bootstrap(tree, zeros, zeros, 0)
        rng = np.random.default_rng(3)
        grads = {k: rng.standard_normal(v.shape).astype(np.float32) * 0.01
                 for k, v in tree.items()}
        chan = InProcessChannel()
        chan.open(layout)
        for step in range(1, steps + 1):
            chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
            for d in chan.poll():
                shadow.on_delivery(d)
        chan.close()
        t0 = time.perf_counter()
        ck = shadow.consolidate(timeout=120)
        drain_s = time.perf_counter() - t0
        st = shadow.stats()
        shadow.shutdown()
        assert ck["step"] == steps
        return {"max_queue_depth": st.max_queue_depth,
                "batched_applies": st.batched_applies,
                "max_batch": st.max_batch,
                "lag_waits": st.lag_waits,
                "lag_wait_s": st.lag_wait_s,
                "drain_s": drain_s}

    bounded = drive(max_lag)
    unbounded = drive(None)
    entry = {
        "workload": f"async shadow, throttled applier "
                    f"({delay_s * 1e3:.0f} ms/apply), {steps} steps",
        "max_lag_steps": max_lag,
        "bounded": bounded,
        "unbounded": unbounded,
    }
    fails = []
    if bounded["max_queue_depth"] > max_lag:
        fails.append(f"bounded-lag queue reached "
                     f"{bounded['max_queue_depth']}, past the bound "
                     f"{max_lag}")
    if unbounded["max_queue_depth"] <= max_lag:
        fails.append("the throttled sequential path never backlogged past "
                     "the bound — the comparison is vacuous")
    if bounded["batched_applies"] < 1:
        fails.append("no multi-step batched catch-up replay ran on the "
                     "bounded-lag path")
    if bounded["drain_s"] >= unbounded["drain_s"]:
        fails.append(f"bounded-lag drain ({bounded['drain_s']:.3f}s) is "
                     f"not faster than the sequential backlog drain "
                     f"({unbounded['drain_s']:.3f}s)")
    return entry, fails


def run_json(out_path: str = "BENCH_shadow.json", steps: int = 8) -> int:
    opt = OptimizerConfig(lr=1e-3)
    params = gpt2_1_5b_leaf_tree()
    layout = layout_for_tree(params)          # default DDP 25 MB cap
    rng = np.random.default_rng(7)
    grad_steps = [{k: rng.standard_normal(v.shape).astype(np.float32) * 0.01
                   for k, v in params.items()} for _ in range(steps + 1)]

    timed = _time_paths(layout, params, grad_steps, opt)
    flat, legacy = timed["flat"], timed["legacy"]
    speedup = legacy["mean_apply_s"] / flat["mean_apply_s"]
    sharded, shard_fails = _sharded_entry(params, grad_steps, opt)
    overlapped, overlap_fails = _overlapped_entry(opt)
    report = {
        "arch": "gpt2-1.5b (per-layer leaf structure, dim-scaled)",
        "n_buckets": len(layout.buckets),
        "n_leaves": sum(len(b.slots) for b in layout.buckets),
        "state_bytes": layout.total_bytes,
        "flat": flat,
        "legacy": legacy,
        "speedup": speedup,
        "sharded": sharded,
        "overlapped": overlapped,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    fails = list(shard_fails) + list(overlap_fails)
    if flat["mean_apply_s"] >= legacy["mean_apply_s"]:
        fails.append("flat apply is not faster than the legacy per-leaf "
                     "path")
    for msg in fails:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if fails else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="flat-vs-legacy apply benchmark; write "
                         "BENCH_shadow.json and gate on flat being faster")
    ap.add_argument("--out", default="BENCH_shadow.json")
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    if args.json:
        sys.exit(run_json(args.out, steps=args.steps))
    run()
