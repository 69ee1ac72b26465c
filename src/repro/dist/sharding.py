"""Logical-axis sharding rules (GSPMD).

Model code never names mesh axes. Parameter specs and activation constraints
use *logical* axis names ("batch", "heads", "act_ff", ...) and this module
resolves them against the physical mesh:

=================  ==========================  ============================
logical axes       physical axes               used by
=================  ==========================  ============================
batch              data axes (pod, data)       activations / inputs
vocab, heads,      model                       tensor-parallel weight dims
kv_heads, ff,
ssm_inner, expert
act_heads, act_ff,  model                      tensor-parallel activations
act_vocab,
act_expert, kv_seq
wemb               fsdp ? data axes : none     the d_model weight dim
everything else    none (replicated)           norms, layers, seq, emb, ...
=================  ==========================  ============================

``fsdp=True`` flips the ``wemb`` weight dim to dp-sharded, which turns every
weight use into an all-gather (ZeRO-3 style) while keeping the same logical
specs — the elastic tests restore one layout onto the other.

A logical dim only shards when its size divides the mapped axes' extent
(GSPMD requires even chunks); otherwise it falls back to replicated, which
is what lets the same model code run on the 1-device smoke mesh and the
16x16 production mesh.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# Logical names that map to the tensor-parallel ("model") axis. Weight dims
# and activation dims are listed separately only for documentation — they
# resolve identically.
_MODEL_AXES = frozenset({
    "vocab", "heads", "kv_heads", "ff", "ssm_inner", "expert",       # weights
    "act_vocab", "act_heads", "act_ff", "act_expert", "kv_seq",      # acts
})

# Logical names that map to the data-parallel axes.
_DATA_AXES = frozenset({"batch"})

# Weight dims that become dp-sharded under FSDP (replicated otherwise).
_FSDP_AXES = frozenset({"wemb"})

# Mesh axes that are NOT data-parallel (everything else contributes to DP).
_NON_DP_MESH_AXES = ("model", "stage")


def dp_axes(mesh) -> tuple[str, ...]:
    """The mesh axes gradients are reduced over (in mesh order)."""
    return tuple(a for a in mesh.axis_names if a not in _NON_DP_MESH_AXES)


def dp_size(mesh) -> int:
    """Total data-parallel extent (the gradient-averaging world size)."""
    return math.prod(mesh.shape[a] for a in dp_axes(mesh))


class ShardingRules:
    """Resolve logical axis names to NamedShardings on a concrete mesh."""

    def __init__(self, mesh, fsdp: bool = False):
        self.mesh = mesh
        self.fsdp = fsdp

    # -- resolution ----------------------------------------------------------
    def physical_axes(self, logical) -> tuple[str, ...]:
        """Mesh axes a logical name maps to (may be empty)."""
        if logical in _DATA_AXES:
            return dp_axes(self.mesh)
        if logical in _MODEL_AXES and "model" in self.mesh.axis_names:
            return ("model",)
        if logical in _FSDP_AXES and self.fsdp:
            return dp_axes(self.mesh)
        return ()

    def axis_size(self, logical) -> int:
        """Extent of the mesh axes behind a logical name (1 if unmapped)."""
        return math.prod(
            (self.mesh.shape[a] for a in self.physical_axes(logical)), start=1)

    def spec(self, *logical, dims=None) -> P:
        """PartitionSpec for one array's logical axes.

        ``dims`` (the array shape) enables the divisibility fallback and the
        one-physical-axis-per-spec guarantee GSPMD requires.
        """
        parts: list = []
        used: set[str] = set()
        for i, name in enumerate(logical):
            axes = self.physical_axes(name) if name is not None else ()
            if any(a in used for a in axes):
                axes = ()               # a physical axis may appear only once
            if axes and dims is not None:
                extent = math.prod(self.mesh.shape[a] for a in axes)
                if dims[i] % extent:
                    axes = ()           # uneven chunks: replicate this dim
            if axes:
                used.update(axes)
                parts.append(axes if len(axes) > 1 else axes[0])
            else:
                parts.append(None)
        while parts and parts[-1] is None:
            parts.pop()                 # trailing Nones are implicit
        return P(*parts)

    def sharding(self, *logical, dims=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical, dims=dims))

    def shard(self, x, *logical):
        """with_sharding_constraint against the resolved logical sharding."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding(*logical, dims=x.shape))


def make_local_mesh(chips: int = 0):
    """(data=chips, model=1) mesh over the first ``chips`` devices present
    (0 = all of them): the data-parallel training mesh of one host."""
    devices = jax.devices()
    chips = chips or len(devices)
    if chips > len(devices):
        raise ValueError(f"{chips} chip(s) requested, {len(devices)} present")
    return jax.make_mesh(
        (chips, 1), ("data", "model"), devices=devices[:chips],
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_smoke_mesh():
    """Single-device ("data", "model") mesh that works on 1 CPU device.

    Smoke tests run the full GSPMD code path (constraints, logical
    resolution, ZeRO-1 specs) with every axis extent 1, so the lowered
    program is collective-free but structurally identical to a pod run.
    """
    return make_local_mesh(1)
