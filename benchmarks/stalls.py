"""Fig 2: iteration time + checkpoint stalls per system when checkpointing
EVERY iteration (GPT-class bench model, real wall-clock on this host).

Paper claims to reproduce (relative): sync stalls worst (9.5x there);
async still stalls (same volume); sharding reduces it; Checkmate ~ no-ckpt.
"""
from __future__ import annotations

import numpy as np

import jax

from benchmarks.common import bench_config, csv_row, smoke_env
from repro.core.buckets import layout_for_tree
from repro.core.checkpoint import (AsyncCheckpointer, CheckmateCheckpointer,
                                   GeminiLikeCheckpointer, NoCheckpointer,
                                   ShardedAsyncCheckpointer, SyncCheckpointer)
from repro.core.shadow import ShadowCluster
from repro.optim import OptimizerConfig
from repro.train.loop import train
from repro.train.step import make_train_state

STEPS, BATCH, SEQ = 6, 8, 128


def run():
    mesh, rules = smoke_env()
    cfg = bench_config("gpt3-xl")
    opt = OptimizerConfig(lr=1e-3)

    def make_ck(name):
        if name == "checkmate":
            s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
            shadow = ShadowCluster(layout_for_tree(s0.params), opt,
                                   n_nodes=2, async_mode=True)
            shadow.bootstrap(s0.params, s0.mu, s0.nu, 0)
            return CheckmateCheckpointer(shadow), s0
        s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
        return {
            "no_checkpoint": NoCheckpointer(),
            "sync": SyncCheckpointer(1),
            "async": AsyncCheckpointer(1),
            "torch_dcp": ShardedAsyncCheckpointer(1, n_shards=4),
            "gemini": GeminiLikeCheckpointer(1),
        }[name], s0

    base_iter = None
    for name in ("no_checkpoint", "checkmate", "sync", "async", "torch_dcp",
                 "gemini"):
        ck, s0 = make_ck(name)
        _, stats = train(cfg, rules, steps=STEPS, batch=BATCH, seq=SEQ,
                         opt=opt, checkpointer=ck, state=s0)
        it = stats.step_s                  # stalls inside
        stall = ck.stall_total / max(ck.n_checkpoints, 1)
        if name == "no_checkpoint":
            base_iter = it
        slowdown = it / base_iter
        csv_row(f"fig2.{name}", it * 1e6,
                f"iter={it*1e3:.0f}ms stall={stall*1e3:.0f}ms "
                f"slowdown={slowdown:.2f}x")
        if hasattr(ck, "shadow"):
            ck.shadow.shutdown()


if __name__ == "__main__":
    run()
