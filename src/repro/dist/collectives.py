"""Explicit ring collectives (paper §2.2; docs/ARCHITECTURE.md "capture point").

Checkmate's capture point exists because a ring AllReduce *is* a
ReduceScatter followed by an AllGather: after the RS phase each device owns
a disjoint, fully-reduced chunk of the gradient — all information needed for
a checkpoint already sits in the network. GSPMD normally emits these
collectives implicitly from sharding constraints (repro.optim.sharded); this
module implements the ring schedule explicitly with ``shard_map`` +
``ppermute`` so tests can assert the exactly-once coverage invariant on the
actual dataflow rather than on compiler output.

Both phases run the classic n-1-step ring: at RS step ``s`` device ``i``
sends chunk ``(i - s - 1) mod n`` and accumulates into ``(i - s - 2) mod n``,
ending with device ``i`` owning fully-reduced chunk ``i``; the AG phase
circulates the owned chunks until everyone holds the full result. Per-chunk
accumulation order is a pure function of ring position, so the reduction is
bitwise deterministic across runs — the property the shadow replay relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ring_all_reduce_rs_ag(x, mesh, axis: str):
    """Ring AllReduce decomposed as ReduceScatter -> AllGather.

    Each device contributes its local value of ``x`` (replicated input =>
    result is ``n * x``). Returns ``(all_reduced, rs_shards)``:

    * ``all_reduced`` — the full reduction, replicated (the AG output),
    * ``rs_shards``   — the same values laid out as the RS phase left them:
      a global array of ``x``'s shape sharded over ``axis``, device ``i``
      owning chunk ``i``. Concatenating the shards IS the AllReduce result —
      the exactly-once gradient coverage Checkmate captures.
    """
    n = mesh.shape[axis]
    if n == 1:
        return x, x

    flat = x.reshape(-1)
    pad = (-flat.size) % n
    padded = jnp.pad(flat, (0, pad)) if pad else flat

    def ring(v):
        idx = jax.lax.axis_index(axis)
        acc = v.reshape(n, -1)
        fwd = [(i, (i + 1) % n) for i in range(n)]

        # -- reduce-scatter: after n-1 steps device i owns reduced chunk i --
        for s in range(n - 1):
            send = jnp.take(acc, (idx - s - 1) % n, axis=0)
            recv = jax.lax.ppermute(send, axis, fwd)
            acc = acc.at[(idx - s - 2) % n].add(recv)
        owned = jnp.take(acc, idx, axis=0)

        # -- all-gather: circulate the reduced chunks around the ring -------
        for s in range(n - 1):
            send = jnp.take(acc, (idx - s) % n, axis=0)
            recv = jax.lax.ppermute(send, axis, fwd)
            acc = acc.at[(idx - s - 1) % n].set(recv)

        return acc.reshape(-1), owned

    full, shards = jax.shard_map(
        ring, mesh=mesh,
        in_specs=P(),                    # every device holds its local copy
        out_specs=(P(), P(axis)),        # replicated result, sharded chunks
        check_vma=False,
    )(padded)

    if pad:
        full = full[:flat.size]
        shards = shards[:flat.size]
    return full.reshape(x.shape), shards.reshape(x.shape)
