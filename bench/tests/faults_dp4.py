#!/usr/bin/env python3
"""The whole run of gpt3-xl.ckpt's job on a ``(data=4, model=1)`` mesh
(ZeRO-1, as the configuration states) past the look for a chip, at a CPU
size on four virtual CPU devices, sound and with each fault such a run can
have planted in the timed path. Prints one JSON object: each run's
``correct`` and the numbers compared.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/faults_dp4.py

`test_bench_faults_dp4.py` runs it so; the process's devices are fixed
when JAX starts, so it cannot run inside the tests' own process.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import repro.train.loop as loop_mod  # noqa: E402
from repro.optim import TrainState  # noqa: E402
from repro.optim.sharded import zero1_spec  # noqa: E402
from bench_tiny import tiny_cell  # noqa: E402
from test_bench_faults import (  # noqa: E402
    answer_altered, break_step, correct, half_batch, token_altered, unchanged)

BATCH = 8       # 2 rows a chip, 1 in each of the 2 tiny microbatches


def four_chip_cell():
    cell = tiny_cell("gpt3-xl.ckpt", batch=BATCH)
    cell.chips = 4
    return cell


def exchange_left_out(monkeypatch):
    """The gradients' exchange between the chips left out: each chip's
    ZeRO-1 slice of the new state and of the captured gradients made from
    its own block of rows alone."""
    build = loop_mod.build_train_step

    def broken(cfg, mesh, rules, *a, **kw):
        step = build(cfg, mesh, rules, *a, **kw)
        n = mesh.shape["data"]

        def stitch(*parts):
            spec = tuple(zero1_spec(parts[0].shape, P(), mesh))
            if not any(spec):
                return parts[0]
            d = next(j for j, s in enumerate(spec) if s is not None)
            size = parts[0].shape[d] // n
            return jnp.concatenate(
                [jax.lax.slice_in_dim(p, i * size, (i + 1) * size, axis=d)
                 for i, p in enumerate(parts)], axis=d)

        def local(state, batch):
            b = batch["tokens"].shape[0] // n
            outs = [step(state, {k: v[i * b:(i + 1) * b]
                                 for k, v in batch.items()})
                    for i in range(n)]
            new = TrainState(
                *(jax.tree.map(stitch, *[getattr(o[0], part) for o in outs])
                  for part in ("params", "mu", "nu")),
                outs[0][0].step)
            metrics = dict(outs[0][1],
                           loss=sum(o[1]["loss"] for o in outs) / n)
            return new, metrics, jax.tree.map(stitch, *[o[2] for o in outs])
        return local
    monkeypatch.setattr(loop_mod, "build_train_step", broken)


def main():
    if len(jax.devices()) < 4:
        sys.exit("needs four devices: set XLA_FLAGS="
                 "--xla_force_host_platform_device_count=4")
    out = {}
    plants = {"sound": None, "exchange_left_out": exchange_left_out}
    for fault in (unchanged, half_batch, token_altered, answer_altered):
        plants[fault.__name__] = (
            lambda mp, fault=fault: break_step(mp, fault))
    for name, plant in plants.items():
        with pytest.MonkeyPatch.context() as mp:
            if plant is not None:
                plant(mp)
            ok, checks = correct(four_chip_cell())
        out[name] = {"correct": ok,
                     "checks": {k: v["value"] for k, v in checks.items()}}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
