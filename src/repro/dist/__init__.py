"""Distributed-training substrate: sharding, collectives, compression, PP.

The data-parallel gradient reduce-scatter this package expresses (via
GSPMD constraints in :mod:`repro.dist.sharding` / :mod:`repro.optim.sharded`
and explicitly in :mod:`repro.dist.collectives`) is Checkmate's capture
point: each device owns a disjoint slice of the fully-reduced gradients, so
the network already carries everything a checkpoint needs.
"""
