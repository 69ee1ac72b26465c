"""GradientChannel delivery API: in-process vs packetized equivalence
(bit-identical shadow state over random layouts/topologies), compressed
bounded divergence (error-feedback invariant), gated-delivery semantics,
capture accounting, consolidation timeouts, and the deprecation shims."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.buckets import layout_for_tree, pack_bucket
from repro.core.channel import (CompressedChannel, InProcessChannel,
                                PacketizedChannel, StepEvent)
from repro.core.checkpoint import SyncCheckpointer
from repro.core.shadow import ShadowCluster
from repro.dist.compression import compress_tree, init_error_feedback
from repro.optim import OptimizerConfig, apply_updates, init_state


def _tree(n_leaves: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {f"leaf{k}": rng.standard_normal((6 + 2 * k, 5))
            .astype(np.float32) for k in range(n_leaves)}


def _drive(channel, layout, params, grad_steps, opt=None, n_nodes=2):
    """Push ``grad_steps`` through ``channel`` into a fresh shadow cluster;
    returns the consolidated checkpoint."""
    opt = opt or OptimizerConfig(lr=1e-3)
    shadow = ShadowCluster(layout, opt, n_nodes=n_nodes)
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    shadow.bootstrap(params, zeros, zeros, 0)
    channel.open(layout)
    for step, grads in enumerate(grad_steps, start=1):
        channel.send(StepEvent(step=step, grads=grads, lr=1e-3))
        for d in channel.poll():
            assert d.complete
            shadow.on_delivery(d)
    channel.close()
    return shadow.consolidate()


# -- equivalence: the transport must not change the checkpoint ---------------

@given(st.integers(1, 4), st.sampled_from([1024, 4096, 1 << 16]),
       st.integers(1, 3), st.sampled_from([1, 2]), st.sampled_from([2, 4]),
       st.sampled_from(["single", "rail-optimized", "leaf-spine"]))
@settings(max_examples=6, deadline=None)
def test_inprocess_packetized_bit_identical(n_leaves, cap, n_nodes,
                                            n_groups, rpg, topo):
    """InProcessChannel and PacketizedChannel (loss-free fabric) produce
    bit-identical ShadowCluster.consolidate() output over random bucket
    layouts, DP-group counts, and topologies."""
    params = _tree(n_leaves, seed=n_leaves * 7 + cap % 97)
    layout = layout_for_tree(params, cap_bytes=cap)
    rng = np.random.default_rng(42)
    grad_steps = [{k: rng.standard_normal(v.shape).astype(np.float32) * 0.01
                   for k, v in params.items()} for _ in range(2)]

    a = _drive(InProcessChannel(), layout, params, grad_steps,
               n_nodes=n_nodes)
    b = _drive(PacketizedChannel(topology=topo, n_dp_groups=n_groups,
                                 ranks_per_group=rpg, ranks_per_leaf=4),
               layout, params, grad_steps, n_nodes=n_nodes)
    assert a["step"] == b["step"] == 2
    for k in a["params"]:
        assert np.array_equal(a["params"][k], b["params"][k]), k
        assert np.array_equal(a["mu"][k], b["mu"][k]), k
        assert np.array_equal(a["nu"][k], b["nu"][k]), k


# -- the wire-buffer pool: reuse only what the shadow gave back --------------

class _NoReuseChannel(InProcessChannel):
    """Deliveries without their lease: nothing is ever given back, so every
    send packs into fresh buffers (the channel before the pool)."""

    def poll(self):
        out = super().poll()
        for d in out:
            d.lease = None
        return out


def _addrs(flats: dict) -> set:
    return {f.ctypes.data for f in flats.values()}


@pytest.mark.parametrize("async_mode", [False, True])
def test_pool_reuses_given_back_buffers_bit_identically(async_mode):
    """From step 3 on, every send packs into buffers that an earlier
    delivery gave back (with lag <= 1 the shadow has applied step k-2 by
    the time step k is sent): the same buffers, and ``bucket.pack``'s
    ``reused`` counts all of its bytes. The shadow ends bit-identical to
    one fed by a channel that never reuses."""
    from repro import obs
    params = _tree(4, seed=11)
    layout = layout_for_tree(params, cap_bytes=256)
    rng = np.random.default_rng(5)
    grad_steps = [{k: rng.standard_normal(v.shape).astype(np.float32)
                   for k, v in params.items()} for _ in range(6)]
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    out = []
    for chan in (InProcessChannel(), _NoReuseChannel()):
        shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2,
                               async_mode=async_mode,
                               max_lag_steps=1 if async_mode else None)
        shadow.bootstrap(params, zeros, zeros, 0)
        chan.open(layout)
        kept, seen, same = [], set(), []   # kept alive: an address is a buffer
        try:
            with obs.enabled_session() as ob:
                for step, grads in enumerate(grad_steps, start=1):
                    chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
                    (d,) = chan.poll()
                    kept.append(d)
                    same.append(_addrs(d.flats) <= seen)
                    seen |= _addrs(d.flats)
                    shadow.on_delivery(d)
                out.append(shadow.consolidate(timeout=60))
                counter = ob.metrics.counter(
                    "channel_pack_reused_bytes_total").value(
                    channel="inprocess")
            reused = [e["args"]["reused"] for e in ob.tracer.events()
                      if e["name"] == "bucket.pack"]
        finally:
            shadow.shutdown()
        assert counter == sum(reused)
        if isinstance(chan, _NoReuseChannel):
            assert not any(same) and not any(reused)
        else:
            assert all(same[2:]) and not same[0]
            assert reused[0] == 0
            assert reused[2:] == [layout.total_bytes] * 4
            assert len(seen) <= 3 * len(layout.buckets)
    a, b = out
    assert a["step"] == b["step"] == 6
    for part in ("params", "mu", "nu"):
        for k in params:
            assert np.array_equal(a[part][k], b[part][k]), (part, k)


def test_pool_never_reuses_what_was_not_given_back():
    """Deliveries that are polled and never applied keep their buffers:
    step 1's flats still hold step 1's gradients after step 2 was sent,
    and step 2 packed into fresh buffers."""
    params = _tree(3, seed=12)
    layout = layout_for_tree(params, cap_bytes=256)
    chan = InProcessChannel()
    chan.open(layout)
    g1 = {k: np.full(v.shape, 1.0, np.float32) for k, v in params.items()}
    g2 = {k: np.full(v.shape, 2.0, np.float32) for k, v in params.items()}
    chan.send(StepEvent(step=1, grads=g1, lr=1e-3))
    (d1,) = chan.poll()
    chan.send(StepEvent(step=2, grads=g2, lr=1e-3))
    (d2,) = chan.poll()
    assert not _addrs(d1.flats) & _addrs(d2.flats)
    for k in params:
        assert np.array_equal(d1.grads[k], g1[k])
        assert np.array_equal(d2.grads[k], g2[k])


def test_a_late_claim_takes_its_buffer_back_or_raises():
    """One delivery may feed a second cluster after the first gave it
    back: the late claim takes the buffers off the free list, so the next
    send packs into fresh ones. Once a send has overwritten them, a claim
    raises instead of applying another step's gradients."""
    params = _tree(2, seed=15)
    layout = layout_for_tree(params, cap_bytes=256)
    ids = [b.bucket_id for b in layout.buckets]
    chan = InProcessChannel()
    chan.open(layout)
    chan.send(StepEvent(step=1, grads=params, lr=1e-3))
    (d1,) = chan.poll()
    d1.lease.claim(ids)
    d1.lease.release(ids)              # the first cluster is done
    d1.lease.claim(ids)                # a second one takes it back
    chan.send(StepEvent(step=2, grads=params, lr=1e-3))
    (d2,) = chan.poll()
    assert not _addrs(d1.flats) & _addrs(d2.flats)
    d1.lease.release(ids)
    chan.send(StepEvent(step=3, grads=params, lr=1e-3))
    (d3,) = chan.poll()
    assert _addrs(d3.flats) == _addrs(d1.flats)
    with pytest.raises(RuntimeError, match="overwritten"):
        d1.lease.claim(ids)


def test_open_clears_the_pool():
    """Buffers given back under one layout are never handed out under the
    next: neither those given back before ``open`` nor a give-back that
    arrives after it. Every buffer of the new layout has its geometry."""
    params = _tree(4, seed=13)
    old, new = (layout_for_tree(params, cap_bytes=c) for c in (128, 1024))
    assert ([b.size for b in old.buckets] != [b.size for b in new.buckets])
    chan = InProcessChannel()
    chan.open(old)
    chan.send(StepEvent(step=1, grads=params, lr=1e-3))
    chan.send(StepEvent(step=2, grads=params, lr=1e-3))
    before, late = chan.poll()
    every = [b.bucket_id for b in old.buckets]
    for d in (before, late):
        d.lease.claim(every)
    before.lease.release(every)
    chan.open(new)
    late.lease.release(every)
    stale = _addrs(before.flats) | _addrs(late.flats)
    ids, prev = [b.bucket_id for b in new.buckets], None
    for step in (3, 4):
        chan.send(StepEvent(step=step, grads=params, lr=1e-3))
        (d,) = chan.poll()
        addrs = _addrs(d.flats)
        assert not addrs & stale
        assert prev is None or addrs == prev    # the new layout's reuse
        for b in new.buckets:
            assert d.flats[b.bucket_id].shape == (b.size,)
        for k in params:
            assert np.array_equal(d.grads[k], params[k])
        d.lease.claim(ids)
        d.lease.release(ids)
        prev = addrs


def test_pool_under_concurrent_give_backs():
    """Many threads give buffers back while the sender packs: a buffer is
    never overwritten while a claim on it is open. Each step's gradients
    are all ``step``; a holder checks its bytes before releasing them."""
    import queue
    import sys
    import threading
    params = {f"w{i}": np.zeros(64, np.float32) for i in range(12)}
    layout = layout_for_tree(params, cap_bytes=256)
    ids = [b.bucket_id for b in layout.buckets]
    n_workers, steps = 16, 300
    chan = InProcessChannel()
    chan.open(layout)
    inboxes = [queue.Queue() for _ in range(n_workers)]
    torn = []

    def hold(inbox, mine):
        while (d := inbox.get()) is not None:
            for bid in mine:
                if not np.all(d.flats[bid] == d.step):
                    torn.append((d.step, bid))
            d.lease.release(mine)

    owned = [ids[w::n_workers] for w in range(n_workers)]
    workers = [threading.Thread(target=hold, args=(inbox, mine), daemon=True)
               for inbox, mine in zip(inboxes, owned) if mine]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in workers:
            t.start()
        for step in range(1, steps + 1):
            grads = {k: np.full(v.shape, step, np.float32)
                     for k, v in params.items()}
            chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
            (d,) = chan.poll()
            for inbox, mine in zip(inboxes, owned):
                if mine:
                    d.lease.claim(mine)
                    inbox.put(d)
        for inbox in inboxes:
            inbox.put(None)
        for t in workers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in workers)
    assert not torn
    assert sum(map(len, chan._pool.free.values())) >= len(ids)


def test_packetized_gated_delivery():
    """A fabric failure surfaces as a gated (complete=False) delivery that
    the shadow refuses; the next step is clean again (one-shot failure)."""
    params = _tree(3, seed=0)
    layout = layout_for_tree(params, cap_bytes=4096)
    chan = PacketizedChannel(ranks_per_group=4, failures_at={2: "capture"})
    chan.open(layout)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2)
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    shadow.bootstrap(params, zeros, zeros, 0)
    for step in (1, 2, 3):
        chan.send(StepEvent(step=step, grads=params, lr=1e-3))
    ds = chan.poll()
    assert [d.complete for d in ds] == [True, False, True]
    assert ds[1].grads is None and ds[1].missing_captures > 0
    assert ds[1].fabric.ring_completed      # training was NOT affected
    with pytest.raises(ValueError, match="gated"):
        shadow.on_delivery(ds[1])


# -- compressed channel: EF bit-identity + bounded divergence ----------------

def test_compressed_channel_matches_reference_stream():
    """The channel's internal compressor is bit-identical to the reference
    compress_tree chain: a training state applying the reference dequantized
    stream equals the shadow state fed through CompressedChannel."""
    params = _tree(3, seed=1)
    layout = layout_for_tree(params, cap_bytes=4096)
    opt = OptimizerConfig(lr=1e-3)
    rng = np.random.default_rng(5)
    raw_steps = [{k: rng.standard_normal(v.shape).astype(np.float32) * 0.01
                  for k, v in params.items()} for _ in range(3)]

    state = init_state({k: jnp.asarray(v) for k, v in params.items()})
    apply_fn = jax.jit(lambda s, g: apply_updates(s, g, opt, 1e-3))
    ef = init_error_feedback(params)
    for raw in raw_steps:
        deq, ef, _ = compress_tree(raw, ef)
        state = apply_fn(state, deq)

    ckpt = _drive(CompressedChannel(InProcessChannel()), layout, params,
                  raw_steps, opt=opt)
    for k in params:
        assert np.array_equal(np.asarray(state.params[k]),
                              ckpt["params"][k]), k


def test_compressed_channel_error_feedback_divergence_bound():
    """With momentum-free SGD the EF invariant is sharp: the shadow (which
    consumed the compressed stream) diverges from raw-gradient training by
    exactly lr * residual — bounded by one quantization step, not by the
    number of iterations."""
    lr = 0.1
    opt = OptimizerConfig(name="sgd", momentum=0.0, lr=lr, weight_decay=0.0)
    params = _tree(2, seed=2)
    layout = layout_for_tree(params, cap_bytes=4096)
    rng = np.random.default_rng(9)
    raw_steps = [{k: rng.standard_normal(v.shape).astype(np.float32)
                  for k, v in params.items()} for _ in range(4)]

    chan = CompressedChannel(InProcessChannel())
    shadow = ShadowCluster(layout, opt, n_nodes=2)
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    shadow.bootstrap(params, zeros, zeros, 0)
    chan.open(layout)
    for step, grads in enumerate(raw_steps, start=1):
        chan.send(StepEvent(step=step, grads=grads, lr=lr))
        for d in chan.poll():
            shadow.on_delivery(d)
    ckpt = shadow.consolidate()

    raw = {k: v.copy() for k, v in params.items()}       # p -= lr * g, f32
    for grads in raw_steps:
        for k in raw:
            raw[k] = (raw[k] - np.float32(lr) * grads[k]).astype(np.float32)

    ef = {k: np.asarray(v) for k, v in chan.compressor.ef.items()}
    for k in params:
        div = ckpt["params"][k] - raw[k]
        # p_shadow - p_raw == lr * ef_T (the un-applied residual mass)
        np.testing.assert_allclose(div, lr * ef[k], atol=5e-6)
        assert np.max(np.abs(div)) <= lr * np.max(np.abs(ef[k])) + 5e-6
    assert chan.compressor.ratio > 3.5               # it really compressed
    assert any(np.any(ckpt["params"][k] != raw[k]) for k in params)


# -- capture accounting (failure drills run through the chaos harness) -------

def test_gated_capture_accounting():
    """A gated capture produces NO checkpoint (neither n_checkpoints nor
    the stall accounting moves; skipped_captures/skipped_steps record it)
    AND desynchronizes the stream: without a resync the shadow refuses
    later applies, staying frozen at the last fully-captured step instead
    of manufacturing a state that skipped the lost gradient. Driven by
    the harness (`resync=False` = events without state_fn); the
    stall-accounting and contiguity invariants check every step."""
    from repro.harness import (ChannelSpec, FabricFailure, FailureSchedule,
                               Scenario, run_scenario)
    sc = Scenario(
        name="gated-capture-frozen", seed=3, steps=3, n_leaves=2,
        shadow_nodes=1, resync=False,
        channel=ChannelSpec(kind="packetized"),
        schedule=FailureSchedule(fabric=(
            FabricFailure(step=2, kind="capture"),)))
    res = run_scenario(sc)
    assert res.passed, res.violations
    ck = res.trace.checkpointer
    assert ck.n_checkpoints == 1
    assert ck.skipped_captures == 2          # the gap AND the refused step 3
    assert ck.skipped_steps == [2, 3]
    # frozen: contiguity preserved at the last fully-captured step
    assert res.trace.final_shadow["step"] == 1
    assert all(r.stall == 0.0 for r in res.trace.records if r.gated)
    assert ck.stall_total == res.trace.records[0].stall  # gated adds none


def test_gated_capture_resyncs_from_state_fn():
    """When the next StepEvent carries state_fn (as the training loop's
    always do — harness `resync=True`), the checkpointer heals the gap
    with a full-state copy: the resync counts as that step's checkpoint
    and the stream resumes."""
    from repro.harness import (ChannelSpec, FabricFailure, FailureSchedule,
                               Scenario, run_scenario)
    sc = Scenario(
        name="gated-capture-resync", seed=3, steps=4, n_leaves=2,
        shadow_nodes=1, resync=True,
        channel=ChannelSpec(kind="packetized"),
        schedule=FailureSchedule(fabric=(
            FabricFailure(step=2, kind="capture"),)))
    res = run_scenario(sc)
    assert res.passed, res.violations
    ck = res.trace.checkpointer
    assert ck.n_checkpoints == 3                 # steps 1, 3 (copy), 4
    assert ck.skipped_captures == 1
    assert ck.skipped_steps == [2]
    assert ck.resyncs == [3]
    assert res.trace.final_shadow["step"] == 4

    # restore() clears the desync too: recovery rewinds training onto the
    # shadow state, so the resumed stream is contiguous by construction
    sc2 = Scenario(
        name="gated-restore-clears-desync", seed=4, steps=2, n_leaves=2,
        shadow_nodes=1, resync=False,
        channel=ChannelSpec(kind="packetized"),
        schedule=FailureSchedule(
            train_fail_steps=(2,),
            fabric=(FabricFailure(step=1, kind="capture"),)))
    res2 = run_scenario(sc2)
    assert res2.passed, res2.violations
    ck2 = res2.trace.checkpointer
    # gated step 1, failure at 2 -> restore() rewound to the bootstrap
    # state (step 0) and both steps replayed cleanly
    replayed = [r for r in res2.trace.records if not r.first_seen]
    assert replayed and replayed[0].restored_step == 0
    assert ck2.n_checkpoints == 2
    assert res2.trace.final_shadow["step"] == 2


class _GatingChannel(InProcessChannel):
    """Reports the deliveries of ``gated`` steps incomplete, as a transport
    that lost part of their capture would; keeps every delivery alive."""

    def __init__(self, gated):
        super().__init__()
        self.gated = set(gated)
        self.seen: dict = {}

    def poll(self):
        out = super().poll()
        for d in out:
            d.complete = d.step not in self.gated
            self.seen[d.step] = d
        return out


@pytest.mark.parametrize("async_mode", [False, True])
def test_refused_deliveries_give_nothing_back(async_mode):
    """The checkpointer refuses a gated delivery, and freezes until a
    resync: the refused delivery gives nothing back, so it keeps its
    gradients and no later send packs into its buffers; the shadow ends
    bit-identical to a fresh one seeded with the resync's state."""
    from repro.core.checkpoint import CheckmateCheckpointer
    params = _tree(3, seed=14)
    layout = layout_for_tree(params, cap_bytes=256)
    opt = OptimizerConfig(lr=1e-3)
    rng = np.random.default_rng(9)
    grads = {s: {k: rng.standard_normal(v.shape).astype(np.float32)
                 for k, v in params.items()} for s in range(1, 7)}
    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    snap = {"params": grads[6], "mu": grads[5],
            "nu": {k: v * v for k, v in grads[5].items()}, "step": 4}
    shadow = ShadowCluster(layout, opt, n_nodes=2, async_mode=async_mode,
                           max_lag_steps=1 if async_mode else None)
    shadow.bootstrap(params, zeros, zeros, 0)
    chan = _GatingChannel(gated={2})
    ck = CheckmateCheckpointer(shadow, channel=chan)
    try:
        for step in range(1, 7):
            ck.on_step(StepEvent(step=step, grads=grads[step], lr=1e-3,
                                 state_fn=(lambda: snap) if step == 4
                                 else None))
        got = shadow.consolidate(timeout=60)
    finally:
        shadow.shutdown()
    assert ck.skipped_steps == [2, 3] and ck.resyncs == [4]
    assert sorted(chan.seen) == [1, 2, 5, 6]   # frozen 3, resync 4: unsent
    refused = _addrs(chan.seen[2].flats)
    for s in (5, 6):
        assert not _addrs(chan.seen[s].flats) & refused
    for b in layout.buckets:
        assert np.array_equal(chan.seen[2].flats[b.bucket_id],
                              pack_bucket(b, grads[2]))
    want = ShadowCluster(layout, opt, n_nodes=1)
    want.bootstrap(snap["params"], snap["mu"], snap["nu"], 4)
    fresh = InProcessChannel()
    fresh.open(layout)
    for s in (5, 6):
        fresh.send(StepEvent(step=s, grads=grads[s], lr=1e-3))
        (d,) = fresh.poll()
        want.on_delivery(d)
    want = want.consolidate()
    assert got["step"] == want["step"] == 6
    for part in ("params", "mu", "nu"):
        for k in params:
            assert np.array_equal(got[part][k], want[part][k]), (part, k)


# -- consolidation timeout ---------------------------------------------------

def test_consolidate_timeout_reports_laggards():
    """A wedged shadow worker can no longer hang recovery: consolidate
    honors its deadline end-to-end and reports the lagging node ids. The
    harness's wedge drill installs the wedge before the final step's
    delivery; the consolidate-timeout invariant checks deadline, laggard
    ids, and the post-release retry."""
    from repro.harness import FailureSchedule, Scenario, run_scenario
    sc = Scenario(
        name="wedge-timeout-laggards", seed=4, steps=2, n_leaves=2,
        shadow_nodes=2, shadow_async=True,
        schedule=FailureSchedule(wedge_node=0, wedge_release_s=1.5))
    res = run_scenario(sc)
    assert res.passed, res.violations
    w = res.trace.wedge
    assert w["raised"]
    assert w["lagging"] == [0]
    assert w["partial_step"] == 1            # min across nodes: stale
    assert w["final_step"] == 2              # worker released: completes


# -- deprecation shims -------------------------------------------------------

def test_deprecated_on_gradients_still_works_and_warns():
    params = _tree(2, seed=6)
    layout = layout_for_tree(params, cap_bytes=4096)
    zeros = {k: np.zeros_like(v) for k, v in params.items()}

    old = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2)
    old.bootstrap(params, zeros, zeros, 0)
    with pytest.warns(DeprecationWarning, match="on_gradients"):
        old.on_gradients(1, 1e-3, params)

    new = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2)
    new.bootstrap(params, zeros, zeros, 0)
    chan = InProcessChannel()
    chan.open(layout)
    chan.send(StepEvent(step=1, grads=params, lr=1e-3))
    for d in chan.poll():
        new.on_delivery(d)

    a, b = old.consolidate(), new.consolidate()
    for k in params:
        assert np.array_equal(a["params"][k], b["params"][k]), k


def test_deprecated_kwarg_on_step_still_works_and_warns():
    st_tree = {"params": {"w": np.ones(64, np.float32)},
               "mu": {"w": np.zeros(64, np.float32)},
               "nu": {"w": np.zeros(64, np.float32)}, "step": 1}
    ck = SyncCheckpointer(freq=1)
    with pytest.warns(DeprecationWarning, match="StepEvent"):
        stall = ck.on_step(1, state_fn=lambda: st_tree, grads=None,
                           lr=1e-3, iter_time=0.01)
    assert stall >= 0.0 and ck.n_checkpoints == 1

    ck2 = SyncCheckpointer(freq=1)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)   # new API must be clean
        ck2.on_step(StepEvent(step=1, state_fn=lambda: st_tree, lr=1e-3))
    assert ck2.n_checkpoints == 1

    with pytest.raises(TypeError):                     # no mixing
        ck2.on_step(StepEvent(step=2, state_fn=lambda: st_tree), lr=1e-3)
