"""End-to-end driver at the ~100M-parameter scale: a GPT-class model trained
with Checkmate per-iteration checkpointing + a mid-run injected failure,
recovered from the shadow cluster.

    PYTHONPATH=src python examples/train_100m.py [--steps 120]

(~112M params; on this single CPU core a step is a few seconds — scale
--steps to taste. On a pod, use repro.launch.train with a full config.)
"""
import argparse
import json
import time
from dataclasses import replace

import numpy as np
import jax

import repro.configs as C
from repro.core.buckets import layout_for_tree
from repro.core.checkpoint import CheckmateCheckpointer
from repro.core.recovery import FailurePlan
from repro.core.shadow import ShadowCluster
from repro.dist.sharding import ShardingRules, make_smoke_mesh
from repro.optim import OptimizerConfig
from repro.optim.schedules import cosine_schedule
from repro.train.loop import train
from repro.train.step import make_train_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at", type=int, default=60)
    args = ap.parse_args()

    cfg = replace(C.get("gpt2-1.5b"),
                  name="gpt2-100m", num_layers=12, d_model=768, num_heads=12,
                  num_kv_heads=12, head_dim=64, d_ff=3072, vocab_size=16384,
                  microbatches=1, attn_q_chunk=128)
    n = cfg.param_count()
    print(f"model: {cfg.name} — {n/1e6:.1f}M params")

    mesh = make_smoke_mesh()
    rules = ShardingRules(mesh)
    opt = OptimizerConfig(lr=3e-4, weight_decay=0.1)
    lr_fn = cosine_schedule(3e-4, warmup=10, total=args.steps)

    state0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    shadow = ShadowCluster(layout_for_tree(state0.params), opt, n_nodes=2,
                           async_mode=True)
    shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)

    t0 = time.time()
    state, stats = train(
        cfg, rules, steps=args.steps, batch=args.batch, seq=args.seq,
        opt=opt, lr_fn=lr_fn, state=state0,
        checkpointer=CheckmateCheckpointer(shadow),
        failure_plan=FailurePlan((args.fail_at,)))
    wall = time.time() - t0

    ckpt = shadow.consolidate()
    s = shadow.stats()
    exact = all(np.array_equal(np.asarray(state.params[k]), ckpt["params"][k])
                for k in state.params)
    print(json.dumps({
        "params_M": round(n / 1e6, 1),
        "steps": stats.steps,
        "loss_first": round(stats.losses[0], 3),
        "loss_last": round(float(np.mean(stats.losses[-5:])), 3),
        "tokens_per_s": round(stats.tokens_per_s, 1),
        "recoveries": stats.recoveries,
        "checkpoints": ckpt["step"],
        "shadow_mean_apply_s": round(s.mean_apply_s, 3),
        "shadow_keeps_up": s.mean_apply_s < stats.step_s,
        "shadow_bit_identical": exact,
        "wall_s": round(wall, 1),
    }, indent=2))
    shadow.shutdown()
    assert exact and stats.losses[-1] < stats.losses[0]


if __name__ == "__main__":
    main()
