"""GradientChannel: the single pluggable delivery API from the capture
point to the shadow apply (paper §4).

Every reduced gradient that reaches the shadow plane flows through one
`GradientChannel`:

    channel.open(layout, multicast_groups)   # once, before training
    channel.send(StepEvent(...))             # per iteration, capture side
    for d in channel.poll():                 # deliveries for the shadow side
        shadow.on_delivery(d)                # (only complete captures apply)
    channel.close()

Every delivery carries the bucket *wire layout* as its primary payload
(``Delivery.flats``: bucket_id -> contiguous flat buffer) — the shadow
applies it with one fused optimizer pass per bucket, and
``Delivery.grads`` stays available as a lazy zero-copy leaf view
(`repro.core.buckets.FlatTreeView`). Three composable implementations
ship here:

* ``InProcessChannel``   — pack-once wire-layout hand-off (the delivery's
                           flats are packed at ``send`` into wire buffers
                           that earlier deliveries gave back, and enqueued
                           by reference).
* ``PacketizedChannel``  — the full paper dataflow: pack buckets
                           (`core.buckets`), segment into MTU frames
                           (`net.packets`), route through the event-driven
                           fabric (`net.simulator.FabricSimulator`) with
                           switch replication per the `core.multicast`
                           group config, and reassemble the capture from
                           the frames that actually arrived at the shadow
                           hosts. An incomplete capture (e.g. a shadow-NIC
                           failure mid-iteration, §4.3.2) surfaces as a
                           gated ``Delivery`` (``complete=False``) — the
                           shadow refuses the partial apply and recovery
                           lands on the last fully-captured step.
* ``CompressedChannel``  — wraps any channel with int8 + error-feedback
                           gradient compression (`dist.compression`); the
                           delivery carries the dequantized stream.

Failure injection, compression, and topology choice are therefore
orthogonal channel options, not bespoke checkpointer code paths.
"""
from __future__ import annotations

import bisect
import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Protocol, runtime_checkable

import numpy as np

from repro import obs as _obs
from repro.core.buckets import (XLA_ALIGN, BucketLayout, FlatTreeView,
                                alloc_flat, bucket_dtype, pack_bucket_into)
from repro.core.multicast import MulticastGroup
from repro.core.multicast import multicast_groups as _make_groups


@dataclass(frozen=True)
class StepEvent:
    """Everything the capture point knows about one training iteration.

    The checkpointer surface consumes this single frozen record
    (``Checkpointer.on_step(event)``) instead of the legacy five-kwarg
    signature.

    Args:
        step: 1-based training step the gradients belong to.
        grads: reduced gradients (host tree) — the multicast payload; None
            for checkpointers that copy state instead (baselines).
        lr: learning rate the training step applied.
        grad_scale: global-norm clipping scale the training step applied.
        iter_time: wall-clock seconds of the iteration (overlap budgets).
        state_fn: zero-arg callable producing a host snapshot of the full
            TrainState — only copy-persist baselines call it.
        flats: the same gradients already in wire layout (bucket_id ->
            contiguous flat buffer, `repro.core.buckets`). Channels that
            receive both use ``flats`` and skip the pack — this is how
            channel wrappers (e.g. `CompressedChannel`) forward an
            already-packed payload without a second pass.
    """
    step: int
    grads: Optional[dict] = None
    lr: float = 0.0
    grad_scale: float = 1.0
    iter_time: Optional[float] = None
    state_fn: Optional[Callable[[], dict]] = None
    flats: Optional[dict] = None


class Delivery:
    """One iteration's gradients as they arrived on the shadow side.

    The primary payload is ``flats`` — the bucket wire layout (bucket_id ->
    contiguous flat buffer) exactly as it left the transport's rx buffer;
    the shadow applies it with one fused optimizer pass per bucket.
    ``grads`` remains available as a backward-compatible *lazy zero-copy*
    leaf view (`repro.core.buckets.FlatTreeView` built over the same
    buffers) — reading a leaf never copies an element.

    ``complete=False`` is a *gated* delivery: the transport could not
    reassemble the full capture (lost mirror frames, dead shadow NIC);
    ``flats``/``grads`` are None and the shadow must not apply it.

    Bucket-sharded transports (``PacketizedChannel(sharded=True)``)
    additionally report *per-owner* verdicts: ``node_complete`` maps each
    shadow node id to whether every bucket it owns was fully reassembled,
    and ``missing_buckets`` maps node id -> tuple of its bucket ids that
    were not. On a partial capture (some owners dead, survivors whole)
    ``complete`` is False but ``flats`` carries the surviving owners'
    buckets, so the shadow can keep the live shard of the cluster current
    (``ShadowCluster.on_delivery(d, nodes=...)``).

    ``lease`` (a `WireLease`, or None) marks flats that are lent from the
    channel's pool of wire buffers. The shadow claims a node's buckets
    when it takes the delivery and gives them back once that node's apply
    has finished; the channel's next ``send`` may then overwrite them. So
    ``flats`` and ``grads`` are valid until the shadow has applied the
    delivery: read them before ``on_delivery``, or copy them. A delivery
    that is never applied keeps its buffers for as long as it lives.
    """

    __slots__ = ("step", "lr", "grad_scale", "complete", "missing_captures",
                 "wire_bytes", "fabric", "flats", "layout", "node_complete",
                 "missing_buckets", "lease", "_grads")

    def __init__(self, step: int, lr: float, grad_scale: float,
                 grads: Optional[dict] = None, complete: bool = True,
                 missing_captures: int = 0, wire_bytes: int = 0,
                 fabric: object = None, flats: Optional[dict] = None,
                 layout: Optional[BucketLayout] = None,
                 node_complete: Optional[dict] = None,
                 missing_buckets: Optional[dict] = None,
                 lease: Optional["WireLease"] = None):
        self.step = step
        self.lr = lr
        self.grad_scale = grad_scale
        self.complete = complete
        self.missing_captures = missing_captures
        self.wire_bytes = wire_bytes
        self.fabric = fabric           # FabricResult for packetized transports
        self.flats = flats
        self.layout = layout
        self.node_complete = node_complete      # sharded: node -> bool
        self.missing_buckets = missing_buckets  # sharded: node -> bucket ids
        self.lease = lease
        self._grads = grads

    @property
    def grads(self) -> Optional[dict]:
        if self._grads is None and self.flats is not None and self.complete:
            self._grads = FlatTreeView(self.layout, self.flats)
        return self._grads

    def __repr__(self):
        return (f"Delivery(step={self.step}, complete={self.complete}, "
                f"wire_bytes={self.wire_bytes})")


@dataclass
class FabricTotals:
    """Always-on cumulative wire/fabric account for one channel.

    Cheap native counters updated in place per send (no registry lookups
    on the hot path); `repro.obs.publish.publish_channel` mirrors them
    into labeled metrics once per run.
    """
    sends: int = 0
    gated: int = 0                      # incomplete captures
    wire_bytes: int = 0                 # incl. in-switch replication
    frames_tx: int = 0
    frames_rx: int = 0
    frames_mirrored: int = 0
    drops: int = 0
    retransmits: int = 0
    rerouted: int = 0
    mirror_lost: int = 0
    pfc_pauses: int = 0
    pfc_resumes: int = 0
    pfc_pause_s: float = 0.0            # aggregate link-paused virtual time
    fabric_time_s: float = 0.0          # simulated time consumed
    link_pfc: dict = field(default_factory=dict)   # per-link pause account

    def absorb(self, result, wire_bytes: int):
        """Fold one ``FabricResult`` into the running totals."""
        self.sends += 1
        if not result.reassembled_ok:
            self.gated += 1
        self.wire_bytes += wire_bytes
        self.frames_tx += result.tx_frames
        self.frames_rx += result.rx_frames
        self.frames_mirrored += result.mirrored_frames
        self.drops += result.drops
        self.retransmits += result.retransmits
        self.rerouted += result.rerouted
        self.mirror_lost += result.mirror_lost_frames
        self.pfc_pauses += result.pfc_pauses
        self.pfc_resumes += result.pfc_resumes
        self.pfc_pause_s += result.pfc_pause_s
        self.fabric_time_s += result.duration_s
        for link, st in result.link_pfc.items():
            agg = self.link_pfc.setdefault(
                link, {"pauses": 0, "resumes": 0, "pause_s": 0.0})
            agg["pauses"] += st["pauses"]
            agg["resumes"] += st["resumes"]
            agg["pause_s"] += st["pause_s"]


@runtime_checkable
class GradientChannel(Protocol):
    """Transport protocol between the capture point and the shadow plane.

    ``send`` returns the *sender-visible stall seconds*: the critical-path
    cost the training step pays to hand the capture off. Work the transport
    performs off the sender's critical path — in-switch replication, wire
    propagation, shadow-side reassembly — is not stall; the fabric's
    virtual-time account lives in ``Delivery.fabric``.

    Channels additionally set ``last_send_parts`` after every ``send``: an
    ordered ``{stage: seconds}`` decomposition of the return value whose
    in-order sum equals it *bit-exactly* (stall attribution,
    `repro.obs.stalls`). Wrappers prepend their own stages to the inner
    channel's parts.
    """
    name: str

    def open(self, layout: BucketLayout,
             multicast_groups: Optional[list[MulticastGroup]] = None
             ) -> None: ...

    def send(self, event: StepEvent) -> float: ...

    def poll(self) -> list[Delivery]: ...

    def close(self) -> None: ...


def _flats_from_event(layout: BucketLayout, event: StepEvent) -> dict:
    """The event's payload in wire layout: reuse ``event.flats`` when the
    sender already packed (channel wrappers), else pack ``event.grads``
    once — the single pass that turns the leaf tree into the native flat
    format every downstream stage consumes."""
    if event.flats is not None:
        return event.flats
    assert event.grads is not None, "channels carry gradients"
    return {b.bucket_id: pack_bucket_into(
                b, event.grads, alloc_flat(b.size, bucket_dtype(b)))
            for b in layout.buckets}


class _WirePool:
    """The free wire buffers of one opened layout: bucket_id -> buffers."""

    def __init__(self):
        self.lock = threading.Lock()
        self.free: dict[int, list] = {}

    def take(self, bucket) -> tuple[np.ndarray, bool]:
        """A buffer for ``bucket``: a given-back one if any, else a new
        one; and whether it was given back."""
        with self.lock:
            free = self.free.get(bucket.bucket_id)
            buf = free.pop() if free else None
        if buf is None:
            return alloc_flat(bucket.size, bucket_dtype(bucket)), False
        return buf, True


class WireLease:
    """One delivery's pooled wire buffers, on loan to the shadow.

    The shadow ``claim``s a node's bucket ids when it takes the delivery
    and ``release``s them once that node's apply has finished. A buffer
    whose every claim is released goes back to its channel's free list,
    where the next ``send`` may overwrite it. A claim that arrives after
    its buffer went back takes it off the free list again, so one delivery
    can feed several clusters; once the buffer has been overwritten the
    claim raises. A buffer nobody claims is never given back or reused.
    """
    __slots__ = ("_pool", "_flats", "_held", "_back")

    def __init__(self, pool: _WirePool, flats: dict):
        self._pool = pool
        self._flats = flats
        self._held = dict.fromkeys(flats, 0)   # bucket_id -> open claims
        self._back: set = set()                # given back to the pool

    def claim(self, bucket_ids):
        with self._pool.lock:
            for bid in bucket_ids:
                if bid in self._back:
                    free = self._pool.free[bid]
                    i = next((i for i, buf in enumerate(free)
                              if buf is self._flats[bid]), None)
                    if i is None:
                        raise RuntimeError(
                            f"bucket {bid}'s wire buffer was given back and "
                            f"overwritten by a later send")
                    del free[i]
                    self._back.discard(bid)
                self._held[bid] += 1

    def release(self, bucket_ids):
        with self._pool.lock:
            for bid in bucket_ids:
                assert self._held[bid] > 0, f"bucket {bid} was not claimed"
                self._held[bid] -= 1
                if not self._held[bid]:
                    self._back.add(bid)
                    self._pool.free.setdefault(bid, []).append(
                        self._flats[bid])


class InProcessChannel:
    """In-process hand-off in wire layout (the paper's loopback shortcut).

    ``send`` packs the gradient tree into per-bucket flat buffers ONCE (or
    adopts ``event.flats`` if the sender already packed) and enqueues those
    buffers by reference; the delivery's ``grads`` is a lazy zero-copy leaf
    view over the very same buffers. ``wire_bytes`` is 0 — nothing crossed
    a wire.

    The pack writes into buffers that the shadow gave back after applying
    earlier deliveries (`WireLease`), and allocates only where none is
    free: a fresh multi-GB buffer costs a page fault per page on first
    touch, which made the pack run at a fraction of the copy rate. The
    ``bucket.pack`` span's ``reused`` arg and the
    ``channel_pack_reused_bytes_total`` counter count the bytes packed into
    given-back buffers. ``open`` starts an empty pool, so a buffer of an
    older layout is never handed out. Adopted ``event.flats`` bypass the
    pool.

    The pack pass is deliberately charged as sender stall: in-process, the
    wire-format copy IS work the sending thread performs (DDP's bucket
    flatten is likewise a training-side copy). The paper's zero-stall
    claim belongs to `PacketizedChannel`, where the capture rides the ring
    AllGather and ``send`` returns 0.0.
    """
    name = "inprocess"

    def __init__(self):
        self._layout: Optional[BucketLayout] = None
        self._pool = _WirePool()
        self._pending: list[Delivery] = []
        self.last_send_parts: dict = {}

    def open(self, layout, multicast_groups=None):
        self._layout = layout
        self._pool = _WirePool()   # older leases give back to the old pool

    def _pack(self, grads: dict) -> tuple[dict, int]:
        """Pack ``grads`` into pooled buffers; the flats and the bytes that
        went into given-back buffers."""
        flats, reused = {}, 0
        for b in self._layout.buckets:
            buf, was_free = self._pool.take(b)
            flats[b.bucket_id] = pack_bucket_into(b, grads, buf)
            reused += buf.nbytes if was_free else 0
        return flats, reused

    def send(self, event: StepEvent) -> float:
        assert self._layout is not None, "open() before send()"
        ob = _obs.get()
        t0 = time.perf_counter()
        pack = {"step": event.step}
        with ob.tracer.span("channel.send", args={"step": event.step,
                                                  "channel": self.name}):
            with ob.tracer.span("bucket.pack", args=pack):
                if event.flats is None:  # adopted flats were packed upstream
                    assert event.grads is not None, \
                        "channels carry gradients"
                    flats, reused = self._pack(event.grads)
                    lease = WireLease(self._pool, flats)
                    pack.update(bytes=self._layout.total_bytes,
                                buckets=len(self._layout.buckets),
                                reused=reused)
                else:
                    flats, lease = event.flats, None
            self._pending.append(Delivery(
                step=event.step, lr=event.lr, grad_scale=event.grad_scale,
                flats=flats, layout=self._layout, complete=True,
                lease=lease))
        dt = time.perf_counter() - t0
        self.last_send_parts = {"send": dt}
        ob.metrics.counter("channel_sends_total", "Gradient sends").inc(
            1, channel=self.name)
        ob.metrics.counter("channel_pack_bytes_total",
                           "Bytes packed into the wire layout").inc(
            pack.get("bytes", 0), channel=self.name)
        ob.metrics.counter("channel_pack_reused_bytes_total",
                           "Bytes packed into given-back wire buffers").inc(
            pack.get("reused", 0), channel=self.name)
        return dt

    def poll(self) -> list[Delivery]:
        out, self._pending = self._pending, []
        return out

    def close(self):
        self._pending.clear()
        self._pool = _WirePool()


def _canon_topology(name: str) -> str:
    aliases = {"rail-optimized": "rail", "rail": "rail",
               "strided": "leaf-spine", "leaf-spine": "leaf-spine",
               "single": "single"}
    if name not in aliases:
        raise ValueError(f"unknown topology {name!r}; "
                         f"expected one of {sorted(set(aliases))}")
    return aliases[name]


class PacketizedChannel:
    """Deliver gradients through the event-driven fabric simulator.

    Per ``send``: the gradient tree is packed into DDP buckets, laid out
    as one contiguous wire buffer, split across DP groups, segmented into
    MTU frames and pushed through one AllGather iteration of
    `FabricSimulator` — boundary-rank frames are DSCP-tagged, the ingress
    leaf's match-action table replicates them toward the shadow hosts, and
    the channel reassembles the capture from the frames that actually
    arrived (via the simulator's frame-level injection/extraction hooks).

    Args:
        topology: "rail-optimized" (alias "rail"), "leaf-spine" (alias
            "strided"), or "single" — see `repro.net.planner`.
        n_dp_groups / ranks_per_group: fabric workload shape; the wire
            buffer is split evenly across groups.
        n_shadow_nodes: shadow hosts on the fabric (transport view; the
            `ShadowCluster` node count is independent).
        replication_factor / n_channels / link_gbps / ranks_per_leaf /
            n_spines / shadow_nics / pfc / frame_quantum: forwarded to the
            simulator (see `FabricSimulator`).
        failures_at: ``{step: failures}`` fabric failure injection; each
            entry fires once (the failed hardware is replaced before the
            post-recovery rerun). ``failures`` is a `FailureSpec` sequence,
            or the string ``"capture"`` — cut every shadow NIC at t=0, so
            the ring completes but that step's capture is lost.
        sharded: bucket-sharded shadow plane — each shadow node owns the
            byte-balanced bucket subset `repro.core.multicast
            .assign_buckets` gives it (the same deterministic map a
            default `ShadowCluster` uses), the fabric routes every
            bucket's frames only to its owner (tagged frames split at
            ownership cuts), and deliveries carry per-owner
            ``node_complete`` / ``missing_buckets`` verdicts plus partial
            flats for the surviving owners.
        shadow_rails: shadow-rail leaf count (`repro.net.planner`); >1
            spreads the sharded owners' incast over independent leaves.
        fast: run each send on the simulator's calendar-queue fast engine
            (bit-identical to the per-frame oracle; see docs/netsim.md).
    """
    name = "packetized"

    def __init__(self, *, topology: str = "rail-optimized",
                 n_dp_groups: int = 1, ranks_per_group: int = 4,
                 n_shadow_nodes: int = 2, replication_factor: int = 1,
                 n_channels: int = 1, link_gbps: float = 100.0,
                 ranks_per_leaf: int = 32, n_spines: int = 2,
                 shadow_nics: int = 2, pfc=None,
                 frame_quantum: Optional[int] = None,
                 failures_at: Optional[dict] = None,
                 sharded: bool = False, shadow_rails: int = 1,
                 fast: bool = False):
        self.topology = _canon_topology(topology)
        self.n_dp_groups = n_dp_groups
        self.ranks_per_group = ranks_per_group
        self.n_shadow_nodes = n_shadow_nodes
        self.replication_factor = replication_factor
        self.n_channels = n_channels
        self.link_gbps = link_gbps
        self.ranks_per_leaf = ranks_per_leaf
        self.n_spines = n_spines
        self.shadow_nics = shadow_nics
        self.pfc = pfc
        self.frame_quantum = frame_quantum
        self.failures_at = dict(failures_at or {})
        self.sharded = sharded
        self.shadow_rails = shadow_rails
        # calendar-queue fast engine vs the per-frame oracle — bit-identical
        # results (tests/test_fabric_fastpath.py), so this is purely a
        # wall-clock knob; recorded in scenario JSON so bundles replay on
        # the exact engine that failed
        self.fast = fast
        self.dead_shadow_nodes: set[int] = set()
        self._owners: Optional[dict] = None   # bucket_id -> owner node
        self._route_starts: list[int] = []    # owner step fn over total buf
        self._route_owners: list[int] = []
        self._bucket_spans: list[tuple] = []  # (bid, start, nbytes, owner)
        self._layout: Optional[BucketLayout] = None
        self._topo = None
        self._groups: Optional[list[MulticastGroup]] = None
        self._pending: list[Delivery] = []
        # derived once at open(), reused every send (perf: send used to
        # re-derive pack metas and reallocate the wire buffer per step)
        self._metas: list[tuple] = []         # (dtype, size, nbytes, offset)
        self._per = 0                         # padded bytes per DP group
        self._total = 0                       # wire buffer size
        self._src_buf: Optional[bytearray] = None
        self._src_views: list[np.ndarray] = []
        self.totals = FabricTotals()
        self.last_send_parts: dict = {}

    def open(self, layout, multicast_groups=None):
        from repro.net.planner import build_topology
        self._layout = layout
        if self.sharded:
            from repro.core.multicast import assign_buckets
            self._owners = assign_buckets(layout, self.n_shadow_nodes)
        self._topo = build_topology(
            self.n_dp_groups, self.ranks_per_group, self.n_shadow_nodes,
            topology=self.topology, ranks_per_leaf=self.ranks_per_leaf,
            link_gbps=self.link_gbps, shadow_nics=self.shadow_nics,
            n_spines=self.n_spines, shadow_rails=self.shadow_rails)
        self._groups = (multicast_groups if multicast_groups is not None
                        else _make_groups(self.n_dp_groups,
                                          self.ranks_per_group,
                                          self.n_shadow_nodes))
        self._set_wire_geometry(tuple(bucket_dtype(b)
                                      for b in layout.buckets))

    def _set_wire_geometry(self, dtypes: tuple):
        """(Re)derive the wire-buffer geometry for per-bucket payload
        ``dtypes`` and allocate the reusable tx buffer.

        Bucket dtypes/sizes/offsets are a function of the layout plus the
        payload dtype (a `CompressedChannel` forwards the dequantized f32
        stand-in even over narrower layouts, and the wire must carry what
        the payload is — never silently downcast). The buffer is padded so
        it splits evenly into n_dp_groups payloads of rpg whole chunks
        each, and each bucket's wire slot starts XLA-aligned so the
        delivery's rx views are adoptable zero-copy by the shadow's fused
        apply.
        """
        self._wire_dtypes = dtypes
        self._metas, cum = [], 0
        for b, dt in zip(self._layout.buckets, dtypes):
            dt = np.dtype(dt)
            nbytes = b.size * dt.itemsize
            cum = -(-cum // XLA_ALIGN) * XLA_ALIGN
            self._metas.append((dt, b.size, nbytes, cum))
            cum += nbytes
        n_g, rpg = self.n_dp_groups, self.ranks_per_group
        self._per = -(-max(cum, n_g * rpg) // (n_g * rpg)) * rpg
        self._total = self._per * n_g
        # the tx wire buffer is allocated once and reused across sends —
        # its bytes are consumed synchronously inside sim.run(); the rx
        # buffer is fresh per send because the delivery's flat views alias
        # it for as long as the consumer holds them
        self._src_buf = bytearray(self._total)
        self._src_views = [
            np.frombuffer(self._src_buf, dtype=dt, count=size, offset=ofs)
            for dt, size, _, ofs in self._metas]
        if self.sharded and self._owners is not None:
            self._shard_geometry()

    def _shard_geometry(self):
        """Derive the owner step-function and per-bucket byte spans over
        the total wire buffer (offsets move when wire dtypes change, so
        this re-runs with ``_set_wire_geometry``)."""
        starts: list[int] = []
        owners: list[int] = []
        spans: list[tuple] = []
        for b, (_dt, _size, nbytes, ofs) in zip(self._layout.buckets,
                                                self._metas):
            o = self._owners[b.bucket_id]
            spans.append((b.bucket_id, ofs, nbytes, o))
            if not owners or o != owners[-1]:
                starts.append(ofs)
                owners.append(o)
        # leading byte 0 and the trailing padding keep their neighbours'
        # owner (padding has no data; its routing just needs to be total)
        starts[0] = 0
        self._route_starts = starts
        self._route_owners = owners
        self._bucket_spans = spans

    def _owner_at(self, off: int) -> int:
        """Shadow node owning total-buffer byte ``off`` (simulator's
        ``shadow_route``)."""
        return self._route_owners[
            bisect.bisect_right(self._route_starts, off) - 1]

    def _node_accounting(self, node_cov: dict, ring_done: bool):
        """Per-owner capture verdicts from the per-node coverage maps.

        ``node_cov``: ``(node_id, replica) -> {total_off: max bytes}`` of
        mirror payloads that actually arrived. Clips every covered span to
        the bucket data spans (wire padding doesn't count), then calls a
        bucket complete when every replica covered all of its bytes.
        """
        starts = [s for _, s, _, _ in self._bucket_spans]
        got: dict[tuple, int] = {}             # (bucket_id, replica) -> B
        for (_nid, rep), seen in node_cov.items():
            for off, ln in seen.items():
                while ln > 0:
                    i = bisect.bisect_right(starts, off) - 1
                    if i < 0:
                        break
                    bid, s, nb, _o = self._bucket_spans[i]
                    end = s + nb
                    if off >= end:             # padding gap: skip ahead
                        if i + 1 >= len(self._bucket_spans):
                            break
                        skip = min(ln, self._bucket_spans[i + 1][1] - off)
                        off += skip
                        ln -= skip
                        continue
                    take = min(ln, end - off)
                    key = (bid, rep)
                    got[key] = got.get(key, 0) + take
                    off += take
                    ln -= take
        rf = self.replication_factor
        missing: dict[int, list] = {n: [] for n in range(self.n_shadow_nodes)}
        for bid, _s, nb, owner in self._bucket_spans:
            if not all(got.get((bid, rep), 0) >= nb for rep in range(rf)):
                missing[owner].append(bid)
        node_complete = {n: ring_done and not missing[n]
                         for n in range(self.n_shadow_nodes)}
        return node_complete, {n: tuple(m) for n, m in missing.items()}

    def kill_shadow_node(self, node_id: int):
        """Persistently cut shadow node ``node_id``'s access NIC: every
        subsequent send loses the frames routed to it, so its buckets stay
        missing until ``revive_all`` (hardware replaced + resync)."""
        if not 0 <= node_id < self.n_shadow_nodes:
            raise ValueError(f"shadow node {node_id} out of range "
                             f"[0, {self.n_shadow_nodes})")
        self.dead_shadow_nodes.add(node_id)

    def revive_all(self):
        """Forget all shadow-node deaths (replacement hardware racked)."""
        self.dead_shadow_nodes.clear()

    def _failures_for(self, step: int):
        from repro.net.simulator import FailureSpec
        # dead shadow nodes stay dead: each send re-cuts their NICs at t=0
        # (every send builds a fresh simulator over the static topology)
        dead = tuple(FailureSpec(0.0, "shadow_nic", n)
                     for n in sorted(self.dead_shadow_nodes))
        spec = self.failures_at.pop(step, None)      # each failure fires once
        if spec is None:
            return dead
        if spec == "capture":
            return dead + tuple(FailureSpec(0.0, "shadow_nic", h)
                                for h in self._topo.shadow_hosts)
        if isinstance(spec, FailureSpec):
            return dead + (spec,)
        return dead + tuple(spec)

    def send(self, event: StepEvent) -> float:
        from repro.net.pfc import PfcConfig
        from repro.net.simulator import FabricSimulator
        assert self._layout is not None, "open() before send()"
        ob = _obs.get()
        send_span = ob.tracer.span("channel.send",
                                   args={"step": event.step,
                                         "channel": self.name})
        send_span.__enter__()

        # one pass: leaves (or an already-packed payload) straight into the
        # reused wire buffer — no intermediate per-bucket concatenate
        buckets = self._layout.buckets
        with ob.tracer.span("bucket.pack", args={"step": event.step}):
            if event.flats is not None:
                dtypes = tuple(np.dtype(event.flats[b.bucket_id].dtype)
                               for b in buckets)
                if dtypes != self._wire_dtypes:  # e.g. f32 dequantized stream
                    self._set_wire_geometry(dtypes)
                for b, dst in zip(buckets, self._src_views):
                    dst[:] = event.flats[b.bucket_id]
            else:
                assert event.grads is not None, "channels carry gradients"
                # the wire carries the GRADIENT dtype (may differ from the
                # param layout's, e.g. f32 grads over a bf16 tree) — exactly
                # what pack_bucket's concatenate would have produced
                dtypes = tuple(
                    np.result_type(*[event.grads[s.name].dtype
                                     for s in b.slots]) for b in buckets)
                if dtypes != self._wire_dtypes:
                    self._set_wire_geometry(dtypes)
                for b, dst in zip(buckets, self._src_views):
                    pack_bucket_into(b, event.grads, dst)
        per, total = self._per, self._total
        src = memoryview(self._src_buf)
        rx_np = alloc_flat(total, np.uint8)      # aligned: views adopt free
        rx = memoryview(rx_np)

        sim = FabricSimulator(
            self._topo, grad_bytes_per_group=per,
            replication_factor=self.replication_factor,
            n_channels=self.n_channels,
            pfc=self.pfc if self.pfc is not None else PfcConfig(),
            failures=self._failures_for(event.step),
            frame_quantum=self.frame_quantum,
            shadow_route=self._owner_at if self.sharded else None,
            shadow_cuts=self._route_starts[1:] if self.sharded else (),
            fast=self.fast)

        def frame_tx(f):                     # injection: slice real bytes in
            off = f.dp_group * per + sim.wire_offset(f)
            f.payload = src[off:off + f.payload_len]

        node_cov: dict = {}   # sharded: (node, replica) -> {total_off: B}

        def shadow_rx(node_id, f):           # extraction: reassemble capture
            off = f.dp_group * per + sim.wire_offset(f)
            rx[off:off + f.payload_len] = f.payload
            if self.sharded:
                seen = node_cov.setdefault((node_id, f.replica), {})
                seen[off] = max(seen.get(off, 0), f.payload_len)

        sim.frame_tx_hook = frame_tx
        sim.shadow_rx_hook = shadow_rx
        rx_frames: list[tuple] = []
        if ob.tracer.enabled:
            # per-frame fabric traversal on the simulated-time tracks:
            # record each mirror delivery (node, virtual tx/arrive times)
            def traced_rx(node_id, f, _inner=shadow_rx):
                _inner(node_id, f)
                rx_frames.append((node_id, f.dp_group, f.chunk, f.replica,
                                  f.t_send, f.t_arrive, f.n_frames,
                                  f.payload_len))
            sim.shadow_rx_hook = traced_rx
        with ob.tracer.span("fabric.simulate", args={"step": event.step}):
            result = sim.run()
        if ob.tracer.enabled:
            tr = ob.tracer
            tr.fabric_span(f"allgather step{event.step}", 0.0,
                           result.duration_s, track="fabric",
                           args={"step": event.step,
                                 "events": result.events,
                                 "reassembled_ok": result.reassembled_ok})
            for nid, dp, chunk, rep, t_tx, t_rx, nf, pl in rx_frames:
                tr.fabric_span(f"g{dp}c{chunk}r{rep}", t_tx, t_rx,
                               track=f"shadow{nid}.rx",
                               args={"step": event.step, "frames": nf,
                                     "bytes": pl})
            tr.fabric_advance(result.duration_s)

        # no live registry incs here: the always-on FabricTotals above is
        # this channel's single metrics source, mirrored into the registry
        # once per run by publish_channel (avoids double counting)
        self.totals.absorb(result, total * self.replication_factor)

        node_complete = missing_buckets = None
        if self.sharded:
            node_complete, missing_buckets = self._node_accounting(
                node_cov, result.ring_completed)

        flats = None
        if result.reassembled_ok:
            # the delivery's flats ARE the rx buffer: zero-copy per-bucket
            # views which keep rx_np alive; Delivery.grads is a lazy leaf
            # view over the same bytes
            flats = {b.bucket_id: rx_np[ofs:ofs + nbytes].view(dt)
                     for b, (dt, _, nbytes, ofs) in zip(buckets, self._metas)}
        elif node_complete is not None and any(node_complete.values()):
            # partial capture: the surviving owners' buckets are whole —
            # ship them so the live shard of the shadow can stay current
            flats = {b.bucket_id: rx_np[ofs:ofs + nbytes].view(dt)
                     for b, (dt, _, nbytes, ofs) in zip(buckets, self._metas)
                     if node_complete[self._owners[b.bucket_id]]}
        self._pending.append(Delivery(
            step=event.step, lr=event.lr, grad_scale=event.grad_scale,
            flats=flats, layout=self._layout,
            complete=result.reassembled_ok,
            missing_captures=result.missing_captures,
            wire_bytes=total * self.replication_factor, fabric=result,
            node_complete=node_complete, missing_buckets=missing_buckets))
        send_span.__exit__(None, None, None)
        # Zero sender-visible stall (§4 zero-overhead claim): the gradient
        # frames ride the ring AllGather training performs anyway, and
        # replication happens in-switch. The event loop above is simulation
        # cost on this host — its virtual-time account is Delivery.fabric.
        self.last_send_parts = {"send": 0.0}
        return 0.0

    def poll(self) -> list[Delivery]:
        out, self._pending = self._pending, []
        return out

    def close(self):
        self._pending.clear()
        self._topo = None
        self._src_buf = None
        self._src_views = []


class CompressedChannel:
    """Wrap any channel with int8 + error-feedback gradient compression.

    ``send`` packs the gradient tree into wire layout once, quantizes the
    flat buckets in a single pass (`dist.compression.Compressor
    .compress_flats`, residuals carried across iterations as flat buffers
    in the same layout), and forwards the *dequantized* flats to the inner
    channel — exactly what a compressed multicast payload delivers, with
    no leaf-dict churn on the hot path. The shadow replica therefore
    tracks the compressed stream; divergence from raw-gradient training is
    bounded by the error-feedback invariant
    (tests/test_compression_shadow.py).

    Quantization runs on the sender's critical path, so ``send`` charges it
    as stall (plus the inner channel's). ``Delivery.wire_bytes`` reports
    the *compressed* payload (int8 + per-leaf scale) — what a compressed
    multicast puts on the wire — even when the inner transport ships the
    dequantized f32 stand-in.

    The error-feedback residual assumes every sent payload is eventually
    consumed; a lossy inner transport is safe because the checkpointer
    enforces stream contiguity — a gated delivery freezes the shadow until
    a full-state resync or recovery, so quantized mass is never silently
    dropped from the stream the shadow applies.
    """
    name = "compressed"

    def __init__(self, inner: Optional[GradientChannel] = None):
        from repro.dist.compression import Compressor
        self.inner: GradientChannel = (inner if inner is not None
                                       else InProcessChannel())
        self.compressor = Compressor()
        self.name = f"compressed[{self.inner.name}]"
        self._layout: Optional[BucketLayout] = None
        self._sent_bytes: dict[int, int] = {}
        self.last_send_parts: dict = {}

    def open(self, layout, multicast_groups=None):
        self._layout = layout
        self.inner.open(layout, multicast_groups)

    def send(self, event: StepEvent) -> float:
        assert self._layout is not None, "open() before send()"
        ob = _obs.get()
        t0 = time.perf_counter()
        with ob.tracer.span("channel.quantize", args={"step": event.step}):
            before = self.compressor.wire_bytes_total
            flats = _flats_from_event(self._layout, event)  # pack once
            deq = self.compressor.compress_flats(self._layout, flats)
        self._sent_bytes[event.step] = (self.compressor.wire_bytes_total
                                        - before)
        stall = time.perf_counter() - t0
        inner_stall = self.inner.send(
            dataclasses.replace(event, grads=None, flats=deq))
        # attribution: quantize + the inner channel's own decomposition
        # (which sums in-order to inner_stall), so the parts' in-order sum
        # equals the stall + inner_stall returned below bit-exactly
        self.last_send_parts = {
            "quantize": stall,
            **dict(getattr(self.inner, "last_send_parts", None)
                   or {"send": float(inner_stall or 0.0)})}
        ob.metrics.counter("channel_wire_bytes_total",
                           "Bytes put on the wire (incl. replication)").inc(
            self._sent_bytes[event.step], channel="compressed")
        return stall + inner_stall

    def poll(self) -> list[Delivery]:
        out = self.inner.poll()
        for d in out:
            d.wire_bytes = self._sent_bytes.pop(d.step, d.wire_bytes)
        return out

    def kill_shadow_node(self, node_id: int):
        """Forward a shadow-node death to the inner (sharded) transport."""
        self.inner.kill_shadow_node(node_id)

    def revive_all(self):
        fn = getattr(self.inner, "revive_all", None)
        if fn is not None:
            fn()

    def close(self):
        self._sent_bytes.clear()
        self.inner.close()
