"""Elastic restore: re-partition the consolidated checkpoint onto a
reconfigured mesh (ROADMAP item 1; Universal Checkpointing / Oobleck).

The shadow's consolidated checkpoint is already a full unsharded tree, so
landing it on a *different* parallelism layout than the run that produced
it needs no data movement beyond the normal restore ``device_put`` — what
has to be rebuilt is everything the old layout *derived*:

* the physical mesh + `ShardingRules` (``mesh_from_plan`` /
  ``rules_from_plan`` realize a `repro.core.costmodel.ElasticPlan`);
* the capture-side `BucketLayout` and the bucket -> shadow-node
  ownership map (under FSDP the RS-shard capture boundary moves with the
  DP width, so channel routing and shadow flats must be re-derived from
  one consistent layout — ``rebuild_shadow``);
* the shadow plane itself: a fresh `ShadowCluster` re-seeded from the
  checkpoint, with the attached `repro.durability.DurableShadow` (if any)
  migrated over — its tiers keep every durable epoch written under the
  old layout, and the re-seed forces a new full base at the resume step
  so ``newest_durable`` never moves backwards;
* the `GradientChannel` + checkpointer wiring
  (`CheckmateCheckpointer.reconfigure`), booked on the stall ledger as
  the named ``elastic-reshard`` stage.

The data stream needs no rebuild: `repro.data.synthetic.SyntheticStream`
materializes the GLOBAL batch as a pure function of (seed, step), and
``device_batch`` re-splits it per the new rules, so global batch order is
preserved across the shrink by construction.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.buckets import layout_for_tree
from repro.core.costmodel import (ElasticMeshBudget, ElasticPlan,
                                  ElasticPlanError, plan_elastic_mesh)
from repro.core.shadow import ShadowCluster
from repro.dist.sharding import ShardingRules

__all__ = ["ElasticMeshBudget", "ElasticPlan", "ElasticPlanError",
           "ELASTIC_STAGE", "plan_elastic_mesh", "mesh_from_plan",
           "rules_from_plan", "rebuild_shadow"]

#: Stall-ledger stage name for the whole plane reconfiguration (channel
#: close/open + shadow swap). Lives in `repro.obs.stalls.KNOWN_STAGES` and
#: the harness stall-attribution vocabulary.
ELASTIC_STAGE = "elastic-reshard"


def mesh_from_plan(plan: ElasticPlan, devices=None):
    """Build the physical mesh an `ElasticPlan` describes.

    ``devices`` defaults to ``jax.devices()``; the plan's survivor ranks
    index into it (lowest-numbered survivors fill the mesh in order).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if plan.n_ranks > len(devices):
        raise ElasticPlanError(
            f"plan needs {plan.n_ranks} device(s) but only "
            f"{len(devices)} are visible")
    picked = [devices[r] for r in plan.survivors] if plan.survivors \
        else devices[:plan.n_ranks]
    return jax.make_mesh(
        plan.mesh_shape, plan.axis_names, devices=picked,
        axis_types=(jax.sharding.AxisType.Auto,) * len(plan.mesh_shape))


def rules_from_plan(plan: ElasticPlan, devices=None) -> ShardingRules:
    """`ShardingRules` for the planned mesh (FSDP flag from the plan)."""
    return ShardingRules(mesh_from_plan(plan, devices), fsdp=plan.fsdp)


def rebuild_shadow(old: ShadowCluster, ckpt: dict, *,
                   n_nodes: Optional[int] = None,
                   cap_bytes: Optional[int] = None,
                   layout=None) -> ShadowCluster:
    """Re-derive the shadow plane for a re-partitioned world.

    Builds a fresh `BucketLayout` from the checkpoint's param tree (the
    capture point may have moved — pass ``cap_bytes`` to keep the old
    bucketing granularity, or ``layout`` to inject one), re-derives the
    bucket ownership map for ``n_nodes`` (default: the old fleet size),
    migrates the attached `DurableShadow` (old durable epochs stay on the
    tiers; the flush bookkeeping carries over so epoch numbering stays
    monotonic), shuts the old cluster down, and seeds the new one from
    ``ckpt`` — which, with durability attached, forces a fresh full base
    at the resume step so a complete restore point exists under the NEW
    layout from the moment the plane re-attaches.
    """
    if layout is None:
        layout = (layout_for_tree(ckpt["params"], cap_bytes)
                  if cap_bytes is not None
                  else layout_for_tree(ckpt["params"]))
    new = ShadowCluster(layout, old.opt,
                        n_nodes=old.n_nodes if n_nodes is None else n_nodes,
                        async_mode=old.async_mode, flat=old.flat)
    dur = old.durability
    old.durability = None          # keep shutdown() from closing the tiers
    if dur is not None:
        dur.reattach(new)          # drains + retires the old flush workers
    old.shutdown()
    new.bootstrap(ckpt["params"], ckpt["mu"], ckpt["nu"],
                  int(ckpt["step"]))
    return new
