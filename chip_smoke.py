#!/usr/bin/env python3
"""On-chip smoke run of the training + Checkmate main path.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # four chips of one host

One chip: trains gpt3-xl at its published widths, cut to LAYERS of its 24
layers, through `repro.launch.train` with the Checkmate checkpointer: GSPMD
train step on the chip -> reduced gradients copied to the host ->
in-process channel -> shadow cluster on the host CPU -> an injected failure
-> restore from the shadow onto the chip. It then checks one checkpoint per
step, one recovery, no shadow lag, peak HBM, that every shadow buffer sits
on the host CPU, and that the shadow's consolidated checkpoint matches the
live training state.

``--chips 4`` runs only the data-parallel path: the same job on a
(data=4, model=1) mesh with ZeRO-1 moments, compared step by step with a
one-chip run of the same global batch, plus the same shadow checks.

Everything runs in this one process. Without a TPU it exits nonzero and
prints no result. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import resource
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "gpt3-xl"
LAYERS = 5           # of 24: the most that leaves one step's gradients of
                     # headroom in v5e's 15.75 GiB (tests/test_tpu_compile.py)
SEQ = 2048
BATCH = 8            # per chip; the config's 4 microbatches of 2 sequences
STEPS = 6
FAIL_AT = 4
HBM_LIMIT = 15.75 * 2**30      # what XLA lets one v5e program use

# Shadow vs live. The CPU tests hold the shadow bit-identical to training.
# Here the trainer's AdamW runs on the TPU and the shadow's on the host CPU,
# both in f32: adds and multiplies round alike, but the TPU's division,
# sqrt and pow (the bias corrections) need not round like the CPU's, and
# the shadow carries its own rounding from step to step. So each applied
# step may move a leaf by a few ulps of its largest value: allow 8 per step.
ULPS_PER_STEP = 8
# Four chips vs one: both compute in bf16 and sum gradients over the batch
# in a different order (per-chip partial sums, then the reduce-scatter).
# bf16 keeps 8 bits, a relative rounding of 2**-8; the losses of the two
# runs may differ by a few such roundings, never by more than 1%.
LOSS_RTOL = 0.01


def job(*extra):
    return ["--arch", ARCH, "--layers", str(LAYERS), "--seq", str(SEQ),
            "--steps", str(STEPS), *map(str, extra)]


def say(*parts):
    print(*parts, flush=True)


def ulp_distance(a, b):
    """Largest distance in f32 units-in-the-last-place between a and b."""
    def ordered(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return int(np.max(np.abs(ordered(a) - ordered(b)), initial=0))


def check_shadow(res, failures):
    """Shadow placement, lag and its consolidated checkpoint vs the live
    training state. Appends what failed to ``failures``."""
    from repro.core.recovery import checkpoint_from_state
    ck = res.checkpointer
    shadow = ck.shadow
    stats = res.stats
    say(f"checkpoints: {ck.n_checkpoints} (steps {stats.steps})")
    say(f"recoveries: {stats.recoveries} (resumed at step "
        f"{stats.recovered_at})")
    lag = shadow.stats().lag
    say(f"shadow lag: {lag}")
    if ck.n_checkpoints != stats.steps or stats.steps != STEPS:
        failures.append("checkpoints != steps")
    if stats.recoveries != 1:
        failures.append("recoveries != 1")
    if lag != 0:
        failures.append("shadow lag != 0")

    devices = {d for n in shadow.nodes for a in n.buffers()
               for d in a.devices()}
    say(f"shadow buffers on: {sorted(str(d) for d in devices)}")
    if {d.platform for d in devices} != {"cpu"}:
        failures.append("shadow state off the host CPU")

    ckpt = shadow.consolidate()
    live = checkpoint_from_state(res.state)
    say(f"step: shadow {ckpt['step']}, live {live['step']}")
    if ckpt["step"] != live["step"]:
        failures.append("shadow step != live step")
    tol = ULPS_PER_STEP * live["step"] * 2.0 ** -23
    n_equal = n_leaves = 0
    for part in ("params", "mu", "nu"):
        for name in sorted(live[part]):
            a = np.asarray(ckpt[part][name])
            b = live[part][name]
            n_leaves += 1
            if np.array_equal(a, b):
                n_equal += 1
            diff = float(np.max(np.abs(a - b), initial=0.0))
            scale = float(np.max(np.abs(b), initial=0.0)) or 1.0
            rel = diff / scale
            say(f"  {part}/{name}: max ulp {ulp_distance(a, b)}, "
                f"max |shadow - live| / max |live| {rel:.3e}")
            if not rel <= tol:
                failures.append(f"{part}/{name} off by {rel:.3e} > {tol:.3e}")
    say(f"shadow vs live: {n_equal} of {n_leaves} leaves bitwise equal; "
        f"tolerance {tol:.3e} of each leaf's largest value "
        f"({ULPS_PER_STEP} ulps x {live['step']} steps)")


def check_losses(losses, failures):
    say("loss per step: " + ", ".join(f"{x:.6f}" for x in losses))
    if not all(math.isfinite(x) for x in losses):
        failures.append("non-finite loss")


def peak_rss_gib():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def memory_report(devices, failures):
    for d in devices:
        peak = d.memory_stats()["peak_bytes_in_use"]
        say(f"peak HBM {d}: {peak / 2**30:.3f} GiB "
            f"(limit {HBM_LIMIT / 2**30:.2f} GiB)")
        if peak >= HBM_LIMIT:
            failures.append(f"peak HBM on {d} over the limit")
    ram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    say(f"host peak RSS: {peak_rss_gib():.3f} GiB of {ram / 2**30:.1f} GiB RAM")


def one_chip(T, jax):
    import repro.configs as C
    cfg = C.get(ARCH)
    say(f"job: {ARCH} d_model {cfg.d_model}, {cfg.num_heads} heads x "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"layers kept {LAYERS} of {cfg.num_layers}; batch {BATCH} x seq "
        f"{SEQ}; {cfg.microbatches} microbatches; {STEPS} steps, failure "
        f"injected at step {FAIL_AT}")
    res = T.run(T.parse_args(job("--batch", BATCH, "--chips", 1,
                                 "--checkpointer", "checkmate",
                                 "--fail-at", FAIL_AT)))
    say("run: " + json.dumps(res.report))
    say(f"host peak RSS after training: {peak_rss_gib():.3f} GiB")
    failures = []
    check_losses(res.stats.losses, failures)
    # random init predicts near-uniformly: the first loss is about ln(vocab)
    if not abs(res.stats.losses[0] - math.log(res.cfg.vocab_size)) < 2.0:
        failures.append("first loss far from ln(vocab)")
    check_shadow(res, failures)
    res.checkpointer.shadow.shutdown()
    memory_report(jax.devices()[:1], failures)
    return failures


def four_chips(T, jax):
    if len(jax.devices()) < 4:
        return [f"--chips 4 needs four devices, found {len(jax.devices())}"]
    import repro.configs as C
    mb = C.get(ARCH).microbatches
    batch = 4 * BATCH
    say(f"job: {ARCH} layers kept {LAYERS} of 24; global batch {batch} x "
        f"seq {SEQ}; (data=4, model=1) mesh, ZeRO-1 moments, {mb} "
        f"microbatches, Checkmate, failure at step {FAIL_AT}; reference: "
        f"one chip, same global batch, {4 * mb} microbatches")
    # the reference runs first, and is freed before the mesh needs chip 0
    ref = T.run(T.parse_args(job("--batch", batch, "--chips", 1,
                                 "--microbatches", 4 * mb,
                                 "--checkpointer", "none")))
    ref_losses = list(ref.stats.losses)
    del ref
    res = T.run(T.parse_args(job("--batch", batch, "--chips", 4,
                                 "--checkpointer", "checkmate",
                                 "--fail-at", FAIL_AT)))
    say("run: " + json.dumps(res.report))
    say(f"host peak RSS after training: {peak_rss_gib():.3f} GiB")
    failures = []
    check_losses(res.stats.losses, failures)
    say("one-chip loss per step: "
        + ", ".join(f"{x:.6f}" for x in ref_losses))
    worst = max(abs(a - b) / abs(b) for a, b in
                zip(res.stats.losses, ref_losses))
    say(f"4 chips vs 1: max relative loss difference {worst:.3e} "
        f"(tolerance {LOSS_RTOL})")
    if len(ref_losses) != len(res.stats.losses) or not worst <= LOSS_RTOL:
        failures.append("4-chip losses disagree with the one-chip run")
    check_shadow(res, failures)
    res.checkpointer.shadow.shutdown()
    memory_report(jax.devices()[:4], failures)
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import train as T
    except ImportError as e:
        sys.exit(f"chip_smoke: the repro package is not beside this "
                 f"script ({e})")
    T.use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
                 f"this smoke run measures nothing off the chip")
    say(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(jax.devices())}")
    failures = (four_chips if args.chips == 4 else one_chip)(T, jax)
    if failures:
        for f in failures:
            say(f"FAILED: {f}")
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
