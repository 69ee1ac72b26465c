"""Bucket-sharded shadow cluster (paper §4.2.4): a sharded consolidate is
bit-identical to the single-node merge for ANY bucket->owner assignment,
the sharded transport routes each bucket's frames only to its owner (and
loses exactly a dead owner's buckets), queue-depth accounting survives
platforms without `queue.qsize`, and every shadow-node-death golden
scenario replays bit-identically through the bundle machinery."""
import dataclasses
import json
import queue
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.buckets import layout_for_tree, pack_bucket
from repro.core.channel import (InProcessChannel, PacketizedChannel,
                                StepEvent)
from repro.core.multicast import assign_buckets
from repro.core.shadow import ShadowCluster, ShadowNodeLoss
from repro.harness import (GOLDEN, Scenario, replay_bundle, run_scenario,
                           write_bundle)
from repro.optim import OptimizerConfig

DEATH_GOLDEN = sorted(n for n, s in GOLDEN.items()
                      if s.schedule.shadow_death)
SHARDED_GOLDEN = sorted(n for n, s in GOLDEN.items() if s.channel.sharded)


def _tree(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    shapes = [(7,), (3, 5), (16,), (2, 2, 3), (11,), (4, 9)]
    return {f"w{i}": rng.standard_normal(s).astype(np.float32) * 0.1
            for i, s in enumerate(shapes)}


def _zeros_like(tree: dict) -> dict:
    return {k: np.zeros_like(v) for k, v in tree.items()}


# -- the regression oracle: sharded == single-node, bit for bit --------------

@given(st.integers(0, 10_000), st.integers(1, 5),
       st.sampled_from(["adamw", "adam", "sgd"]),
       st.sampled_from([False, True]))
@settings(max_examples=10, deadline=None)
def test_sharded_consolidate_matches_single_node(seed, n_nodes, opt_name,
                                                 async_mode):
    """Distributed gather == single-node merge for random bucket->owner
    assignments, node counts, optimizers, and sync/async ingest. The
    1-node cluster (the pre-sharding code path) is the oracle."""
    rng = np.random.default_rng(seed)
    params = _tree(seed)
    layout = layout_for_tree(params, cap_bytes=256)
    assignment = {b.bucket_id: int(rng.integers(0, n_nodes))
                  for b in layout.buckets}
    opt = OptimizerConfig(name=opt_name, lr=1e-3)
    mu, nu = _zeros_like(params), _zeros_like(params)

    oracle = ShadowCluster(layout, opt, n_nodes=1)
    sharded = ShadowCluster(layout, opt, n_nodes=n_nodes,
                            async_mode=async_mode, assignment=assignment)
    oracle.bootstrap(params, mu, nu, 0)
    sharded.bootstrap(params, mu, nu, 0)
    chan = InProcessChannel()
    chan.open(layout)
    try:
        for step in range(1, 4):
            grads = {k: rng.standard_normal(v.shape).astype(np.float32)
                     for k, v in params.items()}
            chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
            for d in chan.poll():
                # safe to share: the apply copies the delivery payload
                # (jnp.asarray) before the donated fused update
                oracle.on_delivery(d)
                sharded.on_delivery(d)
        want = oracle.consolidate()
        got = sharded.consolidate(timeout=60)
        assert got["step"] == want["step"] == 3
        for part in ("params", "mu", "nu"):
            assert set(got[part]) == set(want[part])
            for k in want[part]:
                assert np.array_equal(got[part][k], want[part][k]), \
                    (part, k, n_nodes, opt_name)
    finally:
        sharded.shutdown()


# -- sharded transport: owner routing, death, revival ------------------------

def _sharded_channel(layout, n_nodes=3, **kw):
    chan = PacketizedChannel(topology="rail-optimized", sharded=True,
                             n_shadow_nodes=n_nodes, **kw)
    chan.open(layout)
    return chan


def test_sharded_channel_routes_every_bucket_to_its_owner():
    params = _tree(3)
    layout = layout_for_tree(params, cap_bytes=96)
    owners = assign_buckets(layout, 3)
    assert set(owners.values()) == {0, 1, 2}    # all owners hold shards
    chan = _sharded_channel(layout)
    grads = {k: np.full(v.shape, 0.5, np.float32) for k, v in params.items()}
    chan.send(StepEvent(step=1, grads=grads, lr=1e-3))
    (d,) = chan.poll()
    assert d.complete
    assert d.node_complete == {0: True, 1: True, 2: True}
    assert all(not m for m in d.missing_buckets.values())
    assert set(d.flats) == {b.bucket_id for b in layout.buckets}
    for b in layout.buckets:                    # payload survives the wire
        np.testing.assert_array_equal(np.asarray(d.flats[b.bucket_id]),
                                      pack_bucket(b, grads, xp=np))
    chan.close()


def test_dead_owner_loses_exactly_its_buckets_until_revived():
    params = _tree(4)
    layout = layout_for_tree(params, cap_bytes=96)
    owners = assign_buckets(layout, 3)
    mine = tuple(sorted(b for b, n in owners.items() if n == 1))
    assert mine                                 # node 1 owns something
    chan = _sharded_channel(layout)
    grads = {k: np.ones(v.shape, np.float32) for k, v in params.items()}

    chan.kill_shadow_node(1)
    chan.send(StepEvent(step=1, grads=grads, lr=1e-3))
    (d,) = chan.poll()
    assert not d.complete
    assert d.node_complete == {0: True, 1: False, 2: True}
    assert tuple(d.missing_buckets[1]) == mine  # exactly its buckets
    assert not d.missing_buckets[0] and not d.missing_buckets[2]
    assert set(d.flats) == set(owners) - set(mine)   # survivors' payloads

    # deaths are persistent: the next send loses the same shard again
    chan.send(StepEvent(step=2, grads=grads, lr=1e-3))
    (d2,) = chan.poll()
    assert d2.node_complete[1] is False

    chan.revive_all()                           # replacement racked
    chan.send(StepEvent(step=3, grads=grads, lr=1e-3))
    (d3,) = chan.poll()
    assert d3.complete and all(d3.node_complete.values())
    assert set(d3.flats) == set(owners)
    chan.close()


def test_kill_shadow_node_rejects_unknown_node():
    layout = layout_for_tree(_tree(5), cap_bytes=96)
    chan = _sharded_channel(layout)
    with pytest.raises(ValueError, match="out of range"):
        chan.kill_shadow_node(7)
    chan.close()


def test_cluster_refuses_partial_delivery_for_dead_owner():
    """`on_delivery(nodes=...)` only accepts nodes the transport marked
    complete — asking for a dead owner's apply is an error, not a silent
    skip."""
    params = _tree(6)
    layout = layout_for_tree(params, cap_bytes=96)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=3)
    shadow.bootstrap(params, _zeros_like(params), _zeros_like(params), 0)
    chan = _sharded_channel(layout)
    chan.kill_shadow_node(2)
    grads = {k: np.ones(v.shape, np.float32) for k, v in params.items()}
    chan.send(StepEvent(step=1, grads=grads, lr=1e-3))
    (d,) = chan.poll()
    with pytest.raises(ValueError, match="incomplete for nodes \\[2\\]"):
        shadow.on_delivery(d, nodes={0, 1, 2})
    shadow.on_delivery(d, nodes={0, 1})         # survivors advance
    shadow.kill_node(2)
    with pytest.raises(ShadowNodeLoss) as e:
        shadow.consolidate()
    assert e.value.dead_nodes == [2]
    assert e.value.missing_buckets == {2: tuple(shadow.nodes[2].bucket_ids)}
    assert e.value.partial["step"] == 1         # survivors applied step 1
    chan.close()


# -- the wire-buffer pool on the paths that never apply ----------------------

def _grads(params: dict, step: int) -> dict:
    rng = np.random.default_rng(100 + step)
    return {k: rng.standard_normal(v.shape).astype(np.float32) * 0.1
            for k, v in params.items()}


def _oracle(layout, state: tuple, step0: int, grad_steps: dict) -> dict:
    """A 1-node sync cluster seeded with ``state`` at ``step0``, fed
    ``{step: grads}`` through a channel of its own."""
    oracle = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=1)
    oracle.bootstrap(*state, step0)
    chan = InProcessChannel()
    chan.open(layout)
    for step, grads in grad_steps.items():
        chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
        (d,) = chan.poll()
        oracle.on_delivery(d)
    return oracle.consolidate()


def _assert_same(got: dict, want: dict):
    assert got["step"] == want["step"]
    for part in ("params", "mu", "nu"):
        for k in want[part]:
            assert np.array_equal(got[part][k], want[part][k]), (part, k)


def _addrs(flats: dict, ids=None) -> set:
    return {f.ctypes.data for b, f in flats.items() if ids is None or b in ids}


def test_deliveries_purged_by_bootstrap_give_nothing_back():
    """Deliveries still queued when ``bootstrap`` re-seeds the cluster are
    never applied and never given back: their flats keep their gradients,
    the next send reuses only the buffers of the delivery that was applied,
    and the re-seeded stream ends bit-identical to a fresh oracle."""
    params = _tree(8)
    layout = layout_for_tree(params, cap_bytes=96)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2,
                           async_mode=True)
    shadow.bootstrap(params, _zeros_like(params), _zeros_like(params), 0)
    # each worker holds its first delivery until bootstrap has purged the
    # two queued behind it (its queue's count then drops to the one apply
    # in flight), so the purge is not raced
    started = [threading.Event() for _ in shadow.nodes]
    go = [threading.Event() for _ in shadow.nodes]
    for node in shadow.nodes:
        def held(*a, _o=node._apply, _node=node):
            _node._apply = _o
            started[_node.node_id].set()
            go[_node.node_id].wait(30)
            return _o(*a)
        node._apply = held

    def release_each_after_its_purge():
        for n, q in enumerate(shadow._queues):
            deadline = time.monotonic() + 30
            while shadow._pending(q) != 1 and time.monotonic() < deadline:
                time.sleep(0.001)
            go[n].set()
    chan = InProcessChannel()
    chan.open(layout)
    sent = {}
    try:
        for step in (1, 2, 3):
            sent[step] = _grads(params, step)
            chan.send(StepEvent(step=step, grads=sent[step], lr=1e-3))
            (sent[step, "d"],) = chan.poll()
            shadow.on_delivery(sent[step, "d"])
        assert all(e.wait(30) for e in started)
        threading.Thread(target=release_each_after_its_purge,
                         daemon=True).start()
        seed = (params, _grads(params, 7), _zeros_like(params))
        shadow.bootstrap(*seed, 10)
        purged = _addrs(sent[2, "d"].flats) | _addrs(sent[3, "d"].flats)
        for step in (11, 12):
            sent[step] = _grads(params, step)
            chan.send(StepEvent(step=step, grads=sent[step], lr=1e-3))
            (d,) = chan.poll()
            assert not _addrs(d.flats) & purged
            if step == 11:
                assert _addrs(d.flats) == _addrs(sent[1, "d"].flats)
            shadow.on_delivery(d)
        for step in (2, 3):
            for k in params:
                assert np.array_equal(sent[step, "d"].grads[k],
                                      sent[step][k])
        got = shadow.consolidate(timeout=60)
    finally:
        shadow.shutdown()
    _assert_same(got, _oracle(layout, seed, 10,
                              {s: sent[s] for s in (11, 12)}))


@pytest.mark.parametrize("async_mode", [False, True])
def test_dead_node_gives_nothing_back_until_revived(async_mode):
    """A killed node's buckets are never claimed, so every send while it is
    dead packs them into fresh buffers and the survivors' into given-back
    ones; after the re-seed every bucket reuses again, and the stream ends
    bit-identical to a fresh oracle."""
    params = _tree(9)
    layout = layout_for_tree(params, cap_bytes=96)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2,
                           async_mode=async_mode,
                           max_lag_steps=1 if async_mode else None)
    shadow.bootstrap(params, _zeros_like(params), _zeros_like(params), 0)
    dead = set(shadow.nodes[1].bucket_ids)
    assert dead and set(shadow.nodes[0].bucket_ids)
    chan = InProcessChannel()
    chan.open(layout)
    sent, kept = {}, []
    try:
        for step in range(1, 10):
            if step == 3:
                shadow.consolidate(timeout=60)   # steps 1, 2 given back
                shadow.kill_node(1)
            if step == 7:
                seed = (params, _grads(params, 99), _zeros_like(params))
                shadow.bootstrap(*seed, 6)       # replacement node seeded
            sent[step] = _grads(params, step)
            chan.send(StepEvent(step=step, grads=sent[step], lr=1e-3))
            (d,) = chan.poll()
            before = set().union(*(_addrs(k.flats) for k in kept))
            if step in (5, 6):    # steps 3, 4 took the two given-back sets
                assert not _addrs(d.flats, dead) & before
                assert _addrs(d.flats, set(d.flats) - dead) <= before
            if step == 9:                        # revived: all reused
                assert _addrs(d.flats) <= before
            kept.append(d)
            shadow.on_delivery(d)
        got = shadow.consolidate(timeout=60)
    finally:
        shadow.shutdown()
    _assert_same(got, _oracle(layout, seed, 6,
                              {s: sent[s] for s in (7, 8, 9)}))


# -- queue-depth accounting without queue.qsize ------------------------------

def test_async_ingest_survives_unimplemented_qsize(monkeypatch):
    """Regression: depth tracking used to poll `queue.qsize()`, which is
    both racy and raises NotImplementedError on some platforms (macOS
    sem_getvalue). The mutex-based `unfinished_tasks` count must carry the
    whole async path — ingest, consolidate wait, stats."""
    def boom(self):
        raise NotImplementedError("qsize unavailable on this platform")
    monkeypatch.setattr(queue.Queue, "qsize", boom)

    params = _tree(7)
    layout = layout_for_tree(params, cap_bytes=256)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2,
                           async_mode=True)
    shadow.bootstrap(params, _zeros_like(params), _zeros_like(params), 0)
    chan = InProcessChannel()
    chan.open(layout)
    try:
        for step in range(1, 5):
            grads = {k: np.full(v.shape, 0.1, np.float32)
                     for k, v in params.items()}
            chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
            for d in chan.poll():
                shadow.on_delivery(d)
        ckpt = shadow.consolidate(timeout=30)
        assert ckpt["step"] == 4
        assert shadow.stats().max_queue_depth >= 1   # depth was tracked
    finally:
        shadow.shutdown()


# -- golden death scenarios: replay + bundle round trips ---------------------

def test_corpus_has_enough_death_and_sharded_drills():
    assert len(DEATH_GOLDEN) >= 4
    phases = {d.phase for n in DEATH_GOLDEN
              for d in GOLDEN[n].schedule.shadow_death}
    assert phases == {"step", "consolidate"}
    assert len(SHARDED_GOLDEN) >= len(DEATH_GOLDEN) + 2   # + clean drills


@pytest.mark.parametrize("name", DEATH_GOLDEN)
def test_death_scenarios_replay_bit_identically(name):
    """Each shadow-node-death drill passes every applicable invariant and
    two runs produce byte-identical outcome bundles."""
    a = run_scenario(GOLDEN[name])
    assert a.passed, (name, a.violations)
    b = run_scenario(GOLDEN[name])
    assert a.bundle() == b.bundle()


@pytest.mark.parametrize("name", DEATH_GOLDEN)
def test_death_scenario_json_roundtrip(name):
    sc = GOLDEN[name]
    assert Scenario.from_dict(json.loads(sc.to_json())) == sc


def test_death_violation_bundle_replays(tmp_path):
    """A forced violation on a death scenario rides the write_bundle /
    replay_bundle machinery unchanged (new corpus entries need no new
    plumbing)."""
    sc = dataclasses.replace(GOLDEN["shadow-death-midstep"],
                             name="forced-bit-identity-under-death",
                             invariants=("shadow-bit-identity",
                                         "shadow-node-death"))
    result = run_scenario(sc, bundle_dir=tmp_path)
    if result.passed:
        # bit-identity skips partial trees, so force a real mismatch via
        # the bundle writer directly
        path = write_bundle(result, tmp_path)
    else:
        path = result.bundle_path
    d = json.loads(path.read_text())
    assert Scenario.from_dict(d["scenario"]) == sc
    replayed, identical = replay_bundle(path)
    assert identical
    assert replayed.bundle() == result.bundle()
