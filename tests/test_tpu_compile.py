"""Compile the main path's chip programs for a described TPU v5e.

Nothing runs: the TPU compiler builds each program for a v5e chip that is
described, not attached, and refuses what the chip would refuse (a kernel
Mosaic cannot lower, a program over the chip's memory). The topology is
described inside a fixture, never at import: only one process may load the
TPU library, so describing it while pytest collects would break the other
workers. Keep every such compile in this one file.
"""
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402  (the one-chip job's depth, batch, limit)

V5E_HBM_LIMIT = 15.75 * 2**30          # what XLA lets one v5e program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_fused_adamw_compiles_to_mosaic(one_chip):
    """The shadow's fused AdamW over a real-size bucket (>= 4M f32, not a
    multiple of the tile) lowers to the Mosaic kernel for v5e."""
    from repro.kernels import ops
    n = 4 * 1024 * 1024 + 77
    flat = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = ops.fused_adamw_flat.lower(
        flat, flat, flat, flat, scalar, scalar, block_rows=1024).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gpt3_xl_train_step_fits_one_v5e(topo):
    """chip_smoke.py's job: gpt3-xl at published widths, its depth cut to
    chip_smoke.LAYERS, batch x seq as there. The loop frees each step's
    gradients before the next step, but the program's own analysis counts
    neither the input batch nor the allocator's slack: one step's gradients
    of headroom must remain."""
    import dataclasses
    import repro.configs as C
    from repro.dist.sharding import ShardingRules
    from repro.optim import OptimizerConfig
    from repro.train.step import abstract_train_state, build_train_step

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = dataclasses.replace(C.get(chip_smoke.ARCH),
                              num_layers=chip_smoke.LAYERS)
    rules = ShardingRules(mesh)
    state = abstract_train_state(cfg, rules)
    state.step = jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))
    rows = NamedSharding(mesh, P("data"))
    tokens = jax.ShapeDtypeStruct((chip_smoke.BATCH, chip_smoke.SEQ),
                                  jnp.int32, sharding=rows)
    step = build_train_step(cfg, mesh, rules, OptimizerConfig(),
                            lambda s: 1e-3)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, {"tokens": tokens, "labels": tokens}).compile()

    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    grads = sum(4 * math.prod(a.shape) for a in jax.tree.leaves(state.params))
    assert m.alias_size_in_bytes > 0              # the state is donated
    assert live + grads <= V5E_HBM_LIMIT, (live / 2**30, grads / 2**30)
