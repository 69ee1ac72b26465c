"""Shadow cluster (paper §4.2): CPU replicas that turn captured gradients
into per-iteration checkpoints.

Each shadow node owns a byte-balanced partition of the gradient buckets
(§4.2.4) and holds params + optimizer moments for exactly the leaves in its
buckets. On every iteration it receives that iteration's reduced-gradient
buckets and applies the same functional optimizer step the training nodes
apply — no forward/backward (paper Listing 2):

    while True:
        buckets.recv()
        optimizer.step()

The bucket *wire layout* is the node's native state format: params/mu/nu
live as per-bucket contiguous flat buffers in exactly the layout deliveries
arrive in (`repro.core.buckets`), so an apply is ONE fused optimizer pass
per bucket — `repro.kernels.ops.fused_adamw_flat` for AdamW,
`repro.optim.functional.UPDATE_FNS_FLAT` for the rest — with no per-leaf
dispatch, no dict churn, and no retrace when leaf sets vary (the paper's §5
streaming-apply story: touch each state element exactly once per
iteration). Leaf trees only exist at the cold boundaries: ``bootstrap``
packs them in, ``consolidate`` unpacks them out. ``flat=False`` keeps the
legacy per-leaf path as a regression oracle
(tests/test_flat_shadow.py, benchmarks/shadow_timing.py).

Async mode runs one worker thread per node (the paper's timeliness
requirement §6.3: shadow must finish before training starts the next
optimizer step); queue depth and per-apply wall time are tracked so the
timeliness condition is observable.

Two overlap mechanisms keep a slow applier off the critical path (GoCkpt,
PAPERS.md): the flat apply *double-buffers* deliveries — bucket i+1's
host->device transfer is staged while bucket i's fused update runs — and a
falling-behind async shadow may run with a bounded multi-step lag
(``max_lag_steps``): the worker drains up to K pending deliveries per
wakeup and replays them as K sequential fused updates on the
already-resident flats (bit-identical to K separate applies by
construction — the acceptance bar, see tests/test_flat_shadow.py), while
the trainer blocks only when the backlog would exceed the bound; that wait
is surfaced as the ``apply-lag`` stall stage (obs/stalls.py).

The plane lives on the host CPU, as the paper's shadow cluster does: the
cluster picks ``jax.devices("cpu")[0]`` once and every node places its
state, each staged delivery and each consolidated leaf there with
``jax.device_put``. Nothing inherits the process's default device, which
on a TPU host is the training chip.
"""
from __future__ import annotations

import queue
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.core.buckets import (BucketLayout, alloc_flat, bucket_dtype,
                                pack_bucket, pack_bucket_into, unpack_bucket)
from repro.core.channel import Delivery, InProcessChannel, StepEvent
from repro.core.multicast import assign_buckets
from repro.optim.functional import (OptimizerConfig, UPDATE_FNS,
                                    UPDATE_FNS_FLAT)

APPLY_TIMES_MAXLEN = 512       # recent-apply window kept per node

# consolidate's flat -> leaf cut, compiled once per bucket and run where the
# flat lives. A leaf that spans its whole bucket cuts to the identity; the
# copy keeps it from being the flat buffer that the next apply donates.
_unpack_on_device = jax.jit(
    lambda bucket, flat: {k: jnp.copy(v) for k, v in
                          unpack_bucket(bucket, flat, xp=jnp).items()},
    static_argnums=0)


class ConsolidationTimeout(RuntimeError):
    """Consolidation hit its deadline with shadow nodes still applying.

    Carries the lagging node ids and a partial checkpoint. Each node's
    partition is snapshotted apply-atomically (never torn between params
    and moments), but lagging partitions are at older steps than the rest:
    ``partial["step"]`` is the min across nodes, and the tree as a whole is
    only globally consistent once every node has reached that step — use
    the partial for diagnosis, retry consolidation for recovery."""

    def __init__(self, lagging_nodes: list[int], partial: dict,
                 lagging_buckets: Optional[dict] = None):
        msg = (f"shadow consolidation timed out; lagging nodes: "
               f"{lagging_nodes} (partial checkpoint at step "
               f"{partial.get('step')})")
        if lagging_buckets:
            msg += f"; lagging buckets: {lagging_buckets}"
        super().__init__(msg)
        self.lagging_nodes = lagging_nodes
        self.partial = partial
        # per-node lagging-bucket report: node id -> its owned bucket ids
        self.lagging_buckets = dict(lagging_buckets or {})


class ShadowNodeLoss(RuntimeError):
    """Consolidation found dead shadow nodes: their partitions are gone.

    Unlike :class:`ConsolidationTimeout` (transient — retry), a dead node's
    buckets cannot be gathered until a resync re-seeds a replacement.
    ``missing_buckets`` reports EXACTLY the dead nodes' bucket ids;
    ``partial`` is the surviving nodes' assembled fragments (each
    apply-atomic, at the survivors' current step).

    ``total`` distinguishes losing the ENTIRE plane (partial is empty —
    there is nothing to merge, only the durability tiers can help) from
    partial loss (survivors + durable shards compose). ``durable_hint``
    is ``(tier name, step)`` of the newest full restore point when a
    `repro.durability.DurableShadow` is attached — the message names it
    as the actionable recovery path."""

    def __init__(self, dead_nodes: list[int], missing_buckets: dict,
                 partial: dict, total: bool = False,
                 durable_hint: Optional[tuple] = None):
        msg = (f"shadow node(s) {dead_nodes} lost; missing buckets: "
               f"{missing_buckets} (partial checkpoint at step "
               f"{partial.get('step')})")
        if total:
            msg = (f"TOTAL shadow-plane loss: all {len(dead_nodes)} "
                   f"node(s) {dead_nodes} dead, every bucket missing")
            if durable_hint is not None:
                tname, tstep = durable_hint
                msg += (f"; recover via restore_from_tiers() — newest "
                        f"durable tier '{tname}' holds step {tstep}")
            else:
                msg += ("; no durability tier attached: the checkpoint "
                        "is unrecoverable")
        elif durable_hint is not None:
            tname, tstep = durable_hint
            msg += (f"; tier '{tname}' holds the missing shards durably "
                    f"up to step {tstep}")
        super().__init__(msg)
        self.dead_nodes = list(dead_nodes)
        self.missing_buckets = dict(missing_buckets)
        self.partial = partial
        self.total = bool(total)
        self.durable_hint = durable_hint


class ShadowNode:
    """One CPU shadow node: partition state + functional optimizer.

    ``flat=True`` (default) stores the partition as per-bucket flat
    buffers and applies deliveries with one fused pass per bucket;
    ``flat=False`` is the legacy per-leaf path (regression oracle).
    """

    def __init__(self, node_id: int, opt: OptimizerConfig,
                 layout: BucketLayout, bucket_ids: list[int],
                 device, flat: bool = True,
                 apply_times_maxlen: int = APPLY_TIMES_MAXLEN):
        self.node_id = node_id
        self.opt = opt
        self.layout = layout
        self.device = device           # every state buffer lives here
        self.flat = flat
        self.bucket_ids = sorted(bucket_ids)
        # hot path: resolved once here, not per apply (§6.3 timeliness)
        self._by_id = {b.bucket_id: b for b in layout.buckets}
        ids = set(bucket_ids)
        self._leaves = [s.name for b in layout.buckets
                        if b.bucket_id in ids for s in b.slots]
        # legacy per-leaf state (flat=False)
        self.params: dict[str, jnp.ndarray] = {}
        self.mu: dict[str, jnp.ndarray] = {}
        self.nu: dict[str, jnp.ndarray] = {}
        # flat wire-layout state (flat=True): bucket_id -> flat buffer
        self._pf: dict[int, jnp.ndarray] = {}
        self._mf: dict[int, jnp.ndarray] = {}
        self._vf: dict[int, jnp.ndarray] = {}
        # bucket ids mutated since the last durability flush drained them;
        # maintained under state_lock (repro.durability.FlushWorker)
        self.dirty: set[int] = set()
        self.step = 0
        # bounded recent-apply window + exact running counters (long runs
        # must not grow memory; stats() stays exact via the counters)
        self.apply_times: deque = deque(maxlen=apply_times_maxlen)
        self.apply_count = 0
        self.apply_total_s = 0.0
        self.apply_max_s = 0.0
        # guards the params/mu/nu/step install so a consolidation snapshot
        # never sees a torn partition (params at t+1, moments at t)
        self.state_lock = threading.Lock()
        # Flat updates DONATE p/m/v: the state buffers are updated in place
        # (XLA reuses the donated pages), which matters on the shadow host —
        # the apply is pure memory bandwidth (§5), and re-allocating 3
        # model-sized buffers per step roughly doubles the write traffic.
        # Safe because apply() holds state_lock across the call, so no
        # snapshot can observe a donated (invalidated) buffer.
        if flat:
            if opt.name == "adamw":
                from repro.kernels import ops as _ops
                cfg = opt

                def _adamw(p, g, m, v, step, lr, scale):
                    return _ops.fused_adamw_flat(
                        p, g, m, v, step, lr, scale, b1=cfg.b1, b2=cfg.b2,
                        eps=cfg.eps, wd=cfg.weight_decay)
                self._update_flat = jax.jit(_adamw,
                                            donate_argnums=(0, 2, 3))
            else:
                fn = UPDATE_FNS_FLAT[opt.name]
                self._update_flat = jax.jit(
                    lambda p, g, m, v, step, lr, scale:
                    fn(p, g, m, v, step, self.opt, lr, scale),
                    donate_argnums=(0, 2, 3))
        else:
            self._update = jax.jit(self._update_fn)

    # -- state ---------------------------------------------------------------
    def _put(self, x):
        return jax.device_put(x, self.device)

    def buffers(self) -> list:
        """Every state array this node holds (flat buffers or leaves)."""
        with self.state_lock:
            return [*self._pf.values(), *self._mf.values(),
                    *self._vf.values(), *self.params.values(),
                    *self.mu.values(), *self.nu.values()]

    def bootstrap(self, params, mu, nu, step: int):
        """Install the replica (cold path: leaf trees -> flat partitions)."""
        if self.flat:
            pf, mf, vf = {}, {}, {}
            for bid in self.bucket_ids:
                b = self._by_id[bid]
                pf[bid] = self._put(pack_bucket_into(
                    b, params, alloc_flat(b.size, bucket_dtype(b))))
                mf[bid] = self._put(pack_bucket_into(
                    b, mu, alloc_flat(b.size, np.float32)))
                vf[bid] = self._put(pack_bucket_into(
                    b, nu, alloc_flat(b.size, np.float32)))
            with self.state_lock:
                self._pf, self._mf, self._vf = pf, mf, vf
                self.dirty = set(self.bucket_ids)
                self.step = int(step)
            return
        for name in self._leaves:
            self.params[name] = self._put(params[name])
            self.mu[name] = self._put(mu[name])
            self.nu[name] = self._put(nu[name])
        self.step = int(step)

    def snapshot(self) -> tuple[dict, dict, dict, int]:
        """Apply-atomic (params, mu, nu, step) leaf trees for this
        partition — the cold flat -> leaf boundary used by consolidate.
        The leaves are fresh arrays on the node's device: the next apply
        donates the flat buffers they are cut from."""
        with self.state_lock:
            if not self.flat:
                return dict(self.params), dict(self.mu), dict(self.nu), \
                    self.step
            params, mu, nu = {}, {}, {}
            for bid in self.bucket_ids:
                b = self._by_id[bid]
                params.update(_unpack_on_device(b, self._pf[bid]))
                mu.update(_unpack_on_device(b, self._mf[bid]))
                nu.update(_unpack_on_device(b, self._vf[bid]))
            jax.block_until_ready((params, mu, nu))
            return params, mu, nu, self.step

    def snapshot_dirty(self, force_all: bool = False
                       ) -> tuple[dict, int]:
        """Apply-atomic copy of the dirty bucket flats; drains ``dirty``.

        Returns ``({bucket_id: (p, m, v) np copies}, step)`` in wire
        layout — the durability flush payload, no repacking. The copy
        runs under ``state_lock`` because the fused apply DONATES the
        flat buffers; outside the lock a snapshot could read invalidated
        pages. ``force_all`` snapshots every owned bucket (a base
        record) regardless of dirtiness.
        """
        assert self.flat, "snapshot_dirty requires the flat wire layout"
        with self.state_lock:
            bids = self.bucket_ids if force_all else sorted(self.dirty)
            bids = [b for b in bids if b in self._pf]   # killed: gone
            snap = {bid: (np.array(self._pf[bid]),
                          np.array(self._mf[bid]),
                          np.array(self._vf[bid])) for bid in bids}
            self.dirty.difference_update(bids)
            step = self.step
        return snap, step

    # -- update --------------------------------------------------------------
    def _update_fn(self, params, mu, nu, grads, step, lr, scale):
        fn = UPDATE_FNS[self.opt.name]
        out_p, out_m, out_v = {}, {}, {}
        for name, g in grads.items():
            p, m, v = (fn(params[name], g * scale, mu[name], nu[name],
                          step, self.opt, lr))
            out_p[name], out_m[name], out_v[name] = p, m, v
        return out_p, out_m, out_v

    def _record(self, dt: float):
        self.apply_times.append(dt)
        self.apply_count += 1
        self.apply_total_s += dt
        if dt > self.apply_max_s:
            self.apply_max_s = dt
        _obs.get().metrics.histogram(
            "shadow_apply_seconds",
            "Per-apply wall time by shadow node").observe(
            dt, node=self.node_id)

    def apply(self, step: int, lr: float, flats: dict[int, np.ndarray],
              grad_scale: float = 1.0):
        """Apply one iteration's bucket gradients for this node's partition.

        ``flats`` is the delivery payload in wire layout; only this node's
        ``bucket_ids`` are read. Flat mode runs ONE fused optimizer pass
        per bucket directly on the flat state buffers.
        """
        with _obs.get().tracer.span("shadow.apply",
                                    track=f"shadow{self.node_id}",
                                    args={"step": step,
                                          "node": self.node_id}):
            return self._apply(step, lr, flats, grad_scale)

    def apply_batch(self, items: list[tuple]):
        """Apply K pending deliveries as K *sequential* fused updates on the
        already-resident flats — the bounded-lag catch-up path.

        ``items`` is ``[(step, lr, flats, grad_scale), ...]`` in delivery
        order. Sequential replay (not gradient summing) is deliberate: it is
        bit-identical to K separate :meth:`apply` calls by construction,
        which is the acceptance bar for lagged applies (a summed single
        update would change Adam's moment trajectory). One batched span
        covers the whole drain so catch-up is visible in traces.
        """
        if len(items) == 1:
            step, lr, flats, grad_scale = items[0]
            return self.apply(step, lr, flats, grad_scale)
        with _obs.get().tracer.span("shadow.apply_batch",
                                    track=f"shadow{self.node_id}",
                                    args={"k": len(items),
                                          "from_step": items[0][0],
                                          "to_step": items[-1][0],
                                          "node": self.node_id}):
            for step, lr, flats, grad_scale in items:
                self._apply(step, lr, flats, grad_scale)

    def _apply(self, step, lr, flats, grad_scale):
        t0 = time.perf_counter()
        if self.flat:
            # host scalars: they follow the committed state to its device
            step_f = np.float32(step)
            lr_f = np.float32(lr)
            scale_f = np.float32(grad_scale)
            # the whole update runs under state_lock: inputs are DONATED to
            # the fused kernel, so a concurrent snapshot must never read
            # them mid-apply (it would see invalidated buffers, not a torn
            # tree)
            with self.state_lock:
                ids = self.bucket_ids
                # double-buffered receive: stage bucket i+1's delivery
                # (host->device transfer) before dispatching bucket i's
                # fused update, so the transfer overlaps the async apply;
                # same per-bucket update stream, so bit-identical
                nxt = self._put(flats[ids[0]]) if ids else None
                for j, bid in enumerate(ids):
                    g, nxt = nxt, (self._put(flats[ids[j + 1]])
                                   if j + 1 < len(ids) else None)
                    p, m, v = self._update_flat(
                        self._pf[bid], g,
                        self._mf[bid], self._vf[bid], step_f, lr_f, scale_f)
                    self._pf[bid] = p
                    self._mf[bid] = m
                    self._vf[bid] = v
                jax.block_until_ready(self._pf)
                self.dirty.update(self.bucket_ids)
                self.step = step
            self._record(time.perf_counter() - t0)
            return
        grads = {}
        for bid in self.bucket_ids:
            bucket = self._by_id[bid]
            grads.update(unpack_bucket(bucket, self._put(flats[bid]),
                                       xp=jnp))
        grads = {k: v for k, v in grads.items() if k in self.params}
        p, m, v = self._update(self.params, self.mu, self.nu, grads,
                               np.float32(step), np.float32(lr),
                               np.float32(grad_scale))
        jax.block_until_ready(p)
        with self.state_lock:
            self.params.update(p)
            self.mu.update(m)
            self.nu.update(v)
            self.step = step
        self._record(time.perf_counter() - t0)


@dataclass
class ShadowStats:
    steps_applied: int
    lag: int                       # training step - shadow step
    max_queue_depth: int
    mean_apply_s: float
    max_apply_s: float
    per_node_apply_s: list[float]
    # bounded-lag accounting (max_lag_steps runs; defaults keep the
    # legacy construction sites valid)
    lag_waits: int = 0             # times the trainer blocked on the bound
    lag_wait_s: float = 0.0        # total seconds the trainer waited
    batched_applies: int = 0       # multi-step worker drains (k >= 2)
    max_batch: int = 1             # largest k a single drain replayed


class ShadowCluster:
    """Checkmate's shadow plane: N nodes x partitioned functional optimizer."""

    def __init__(self, layout: BucketLayout, opt: OptimizerConfig,
                 n_nodes: int = 1, async_mode: bool = False,
                 flat: bool = True,
                 apply_times_maxlen: int = APPLY_TIMES_MAXLEN,
                 assignment: Optional[dict] = None,
                 max_lag_steps: Optional[int] = None):
        if max_lag_steps is not None:
            if max_lag_steps < 1:
                raise ValueError(f"max_lag_steps must be >= 1, "
                                 f"got {max_lag_steps}")
            if not async_mode:
                raise ValueError("max_lag_steps bounds the async delivery "
                                 "queue; sync mode never lags")
        self.layout = layout
        self.opt = opt
        self.n_nodes = n_nodes
        self.flat = flat
        # bucket_id -> owner node; the default byte-balanced greedy mapping
        # is the one training nodes, switch, and channel all derive, but a
        # custom assignment may be injected (tests sweep random mappings)
        self.assignment = dict(assignment) if assignment is not None \
            else assign_buckets(layout, n_nodes)
        # the one placement decision: the whole plane on the host CPU
        self.device = jax.devices("cpu")[0]
        self.nodes = [
            ShadowNode(i, opt, layout,
                       [b for b, n in self.assignment.items() if n == i],
                       self.device, flat=flat,
                       apply_times_maxlen=apply_times_maxlen)
            for i in range(n_nodes)
        ]
        self.async_mode = async_mode
        self.train_step_seen = 0
        self.max_queue_depth = 0
        self.dead_nodes: set[int] = set()
        # bounded multi-step lag (None = legacy unbounded queue): a worker
        # drains up to max_lag_steps pending deliveries per wakeup and the
        # trainer blocks in _ingest while a node's backlog is at the bound
        self.max_lag_steps = max_lag_steps
        self.lag_waits = 0
        self.lag_wait_s_total = 0.0
        self.batched_applies = 0
        self.max_batch = 1
        # optional repro.durability.DurableShadow (set by its attach());
        # duck-typed so core never imports the durability package
        self.durability = None
        self._queues: list[queue.Queue] = []
        self._drained: list[threading.Event] = []
        self._lag_cvs: list[threading.Condition] = []
        self._workers: list[threading.Thread] = []
        if async_mode:
            self._start_workers()

    # -- async plumbing --------------------------------------------------------
    def _start_workers(self):
        for node in self.nodes:
            q: queue.Queue = queue.Queue()
            ev = threading.Event()
            ev.set()                           # empty queue == drained
            t = threading.Thread(target=self._worker, args=(node, q, ev),
                                 daemon=True)
            t.start()
            self._queues.append(q)
            self._drained.append(ev)
            self._lag_cvs.append(threading.Condition())
            self._workers.append(t)

    def _worker(self, node: ShadowNode, q: queue.Queue,
                drained: threading.Event):
        by_id = node._by_id
        # batched drain bound: a bounded-lag shadow catches up by replaying
        # up to K pending deliveries per wakeup; legacy (None) keeps the
        # exact one-item-per-wakeup behavior
        limit = self.max_lag_steps or 1
        while True:
            item = q.get()
            stop = item is None
            batch = [] if stop else [item]
            while not stop and len(batch) < limit:
                try:
                    nxt = q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    stop = True           # shutdown sentinel: drain then exit
                    break
                batch.append(nxt)
            if batch and node.node_id in self.dead_nodes:
                # killed after these items were enqueued: its state is gone,
                # applying would read a cleared partition
                self._settle(node.node_id, q, drained, len(batch))
                batch = []
            if batch:
                items = []
                for step, lr, scale, grads, flats, _ in batch:
                    if flats is None:
                        # legacy leaf-tree hand-off: bucket packing happens
                        # HERE, on the shadow node — the caller only
                        # enqueued a reference
                        flats = {bid: pack_bucket(by_id[bid], grads, xp=np)
                                 for bid in node.bucket_ids}
                    items.append((step, lr, flats, scale))
                node.apply_batch(items)
                for *_, lease in batch:     # applied: the buffers go back
                    if lease is not None:
                        lease.release(node.bucket_ids)
                if len(items) > 1:
                    self.batched_applies += 1
                    if len(items) > self.max_batch:
                        self.max_batch = len(items)
                self._settle(node.node_id, q, drained,
                             len(batch) + (1 if stop else 0))
            elif stop:
                self._settle(node.node_id, q, drained, 1)
            if stop:
                drained.set()
                return

    def _settle(self, node_id: int, q: queue.Queue,
                drained: threading.Event, n: int):
        """Mark ``n`` queue items done, refresh the drain signal, and wake a
        trainer blocked on the lag bound (checked under the queue lock)."""
        for _ in range(n):
            q.task_done()
        # drain signal for the event-based consolidate wait: set only
        # when no enqueued work remains
        with q.mutex:
            if q.unfinished_tasks == 0:
                drained.set()
        if self.max_lag_steps is not None:
            cv = self._lag_cvs[node_id]
            with cv:
                cv.notify_all()

    # -- API -------------------------------------------------------------------
    def bootstrap(self, params, mu, nu, step: int = 0):
        """Install the initial replica (paper: shadow starts from a copy).

        Also the node-replacement path: re-seeding revives any nodes
        previously lost to :meth:`kill_node` (the resync that follows a
        shadow-node death hands every node a fresh partition).
        """
        span = _obs.get().tracer.span
        with span("shadow.bootstrap", track="shadow",
                  args={"step": int(step)}):
            d2h = {}
            with span("shadow.bootstrap.d2h", track="shadow", args=d2h):
                params, mu, nu = ({k: np.asarray(v) for k, v in t.items()}
                                  for t in (params, mu, nu))
                d2h["bytes"] = sum(v.nbytes for t in (params, mu, nu)
                                   for v in t.values())
            # a full-state install supersedes any still-queued deliveries:
            # with a lagged backlog, replaying a pre-resync gradient onto
            # the freshly seeded state would regress it (no-op when queues
            # are drained, the normal case)
            for q in self._queues:
                try:
                    while True:
                        item = q.get_nowait()
                        if item is None:      # never eat a shutdown sentinel
                            q.put(None)       # (task_done below pairs our
                        q.task_done()         # get with the re-put's inc)
                        if item is None:
                            break
                except queue.Empty:
                    pass
                while self._pending(q):       # an in-flight apply (already
                    time.sleep(0.001)         # off the queue) finishes on
                #                               the OLD state before the
                #                               install below
            self.dead_nodes.clear()
            for node in self.nodes:
                with span("shadow.bootstrap.install", track="shadow",
                          args={"node": node.node_id}):
                    node.bootstrap(params, mu, nu, step)
            self.train_step_seen = int(step)
            if self.durability is not None:
                # cold path: force a base flush so a full restore point
                # exists from the moment the replica is (re-)seeded
                self.durability.on_bootstrap(int(step))

    def kill_node(self, node_id: int):
        """Simulated shadow-node death: the node's partition (params + both
        moments) is gone, as lost DRAM is. Pending queued work for the node
        is discarded; a later :meth:`bootstrap` re-seeds a replacement.
        """
        if node_id in self.dead_nodes:
            return
        if not 0 <= node_id < self.n_nodes:
            raise ValueError(f"no shadow node {node_id} "
                             f"(cluster has {self.n_nodes})")
        self.dead_nodes.add(node_id)
        node = self.nodes[node_id]
        if self.async_mode:
            q, ev = self._queues[node_id], self._drained[node_id]
            try:
                while True:
                    q.get_nowait()
                    q.task_done()
            except queue.Empty:
                pass
            with q.mutex:
                if q.unfinished_tasks == 0:
                    ev.set()
            if self.max_lag_steps is not None:
                cv = self._lag_cvs[node_id]
                with cv:          # wake a trainer blocked on the dead node
                    cv.notify_all()
        with node.state_lock:     # an in-flight apply finishes first
            node._pf, node._mf, node._vf = {}, {}, {}
            node.params, node.mu, node.nu = {}, {}, {}
        _obs.get().metrics.counter(
            "shadow_node_deaths_total",
            "Shadow nodes lost (partition dropped)").inc(1, node=node_id)

    def on_delivery(self, delivery: Delivery, nodes: Optional[set] = None):
        """Consume one channel delivery (the ONLY gradient ingress).

        The delivery's ``flats`` (wire layout) feed the fused per-bucket
        apply directly — no unpack, no repack. Gated deliveries
        (``complete=False``) must be filtered by the caller — the shadow
        refuses a partial apply.

        ``nodes`` restricts the apply to a subset of node ids (the sharded
        transport's per-node gating: a delivery may be complete for some
        owners and not others — see ``Delivery.node_complete``). Every
        requested node must be complete; without ``nodes`` the delivery
        must be globally complete.
        """
        if nodes is not None:
            nc = getattr(delivery, "node_complete", None)
            bad = sorted(n for n in nodes
                         if not (delivery.complete if nc is None
                                 else nc.get(n, False)))
            if bad:
                raise ValueError(
                    f"refusing sharded delivery for step {delivery.step}: "
                    f"capture incomplete for nodes {bad}")
        elif not delivery.complete:
            raise ValueError(
                f"refusing gated delivery for step {delivery.step}: "
                f"capture incomplete ({delivery.missing_captures} missing)")
        if delivery.flats is not None:
            self._ingest(delivery.step, delivery.lr, None,
                         delivery.grad_scale, flats=delivery.flats,
                         nodes=nodes, lease=delivery.lease)
        else:
            self._ingest(delivery.step, delivery.lr, delivery.grads,
                         delivery.grad_scale, nodes=nodes)

    def on_gradients(self, step: int, lr: float, grads: dict,
                     grad_scale: float = 1.0):
        """Deprecated direct hand-off; route gradients through a
        `repro.core.channel.GradientChannel` and `on_delivery` instead."""
        warnings.warn(
            "ShadowCluster.on_gradients is deprecated; deliver gradients "
            "through a repro.core.channel.GradientChannel and call "
            "ShadowCluster.on_delivery",
            DeprecationWarning, stacklevel=2)
        self._ingest(step, lr, grads, grad_scale)

    def _ingest(self, step: int, lr: float, grads: Optional[dict],
                grad_scale: float = 1.0,
                flats: Optional[dict] = None,
                nodes: Optional[set] = None,
                lease=None):
        """Apply one iteration's reduced gradients, each node its partition.

        ``flats`` (the wire-layout delivery payload) is handed to nodes as
        is — zero copies between the channel rx buffer and the fused apply
        — and each node sees ONLY its owned buckets (the sharded transport
        may not even have the others). Async mode enqueues a REFERENCE only
        — any (legacy) packing and the optimizer replay run on the shadow
        workers, off the training critical path. Each node claims its
        buckets of ``lease`` (`repro.core.channel.WireLease`) here and
        releases them once its apply has finished.
        """
        self.train_step_seen = step
        targets = [n for n in self.nodes
                   if n.node_id not in self.dead_nodes
                   and (nodes is None or n.node_id in nodes)]
        if self.async_mode:
            for node in targets:
                q = self._queues[node.node_id]
                if self.max_lag_steps is not None:
                    self._lag_gate(node.node_id, q)
                self._drained[node.node_id].clear()
                sub = None if flats is None else \
                    {bid: flats[bid] for bid in node.bucket_ids}
                if lease is not None:
                    lease.claim(node.bucket_ids)
                q.put((step, lr, grad_scale, grads, sub, lease))
                # mutex-based depth (queue.qsize() is racy and unimplemented
                # on some platforms); put() precedes, so depth >= 1 here
                depth = self._pending(q)
                self.max_queue_depth = max(self.max_queue_depth, depth)
                if self.max_lag_steps is not None:
                    _obs.get().metrics.gauge(
                        "shadow_lag_steps",
                        "Shadow applier backlog at ingest (bounded by "
                        "max_lag_steps)").set(depth, node=node.node_id)
            if self.durability is not None:
                self.durability.notify(step)      # queue puts only
            return
        if flats is None:
            need = {bid for node in targets for bid in node.bucket_ids}
            flats = {b.bucket_id: pack_bucket(b, grads, xp=np)
                     for b in self.layout.buckets if b.bucket_id in need}
        for node in targets:
            if lease is not None:
                lease.claim(node.bucket_ids)
            node.apply(step, lr,
                       {bid: flats[bid] for bid in node.bucket_ids},
                       grad_scale)
            if lease is not None:
                lease.release(node.bucket_ids)
        if self.durability is not None:
            self.durability.notify(step)          # queue puts only

    @staticmethod
    def _pending(q: queue.Queue) -> int:
        with q.mutex:
            return q.unfinished_tasks

    def _lag_gate(self, node_id: int, q: queue.Queue):
        """Block the caller (the trainer's ingest) while ``node_id``'s
        backlog is at the lag bound — this wait IS the bounded-lag
        contract: the shadow may trail by at most ``max_lag_steps``
        iterations, and any time the trainer spends here is booked by the
        checkpointer as the ``apply-lag`` stall stage."""
        limit = self.max_lag_steps
        if self._pending(q) < limit or node_id in self.dead_nodes:
            return
        t0 = time.perf_counter()
        cv = self._lag_cvs[node_id]
        with cv:
            # timed wait (not bare) so a node killed mid-wait can't strand
            # the trainer: the dead check re-runs each wakeup
            while (self._pending(q) >= limit
                   and node_id not in self.dead_nodes):
                cv.wait(0.05)
        dt = time.perf_counter() - t0
        self.lag_waits += 1
        self.lag_wait_s_total += dt
        _obs.get().metrics.counter(
            "shadow_lag_wait_seconds_total",
            "Trainer wait for a backlogged shadow applier "
            "(the apply-lag stall stage)").inc(dt, node=node_id)

    def consolidate(self, timeout: Optional[float] = None) -> dict:
        """Distributed gather: reassemble a full checkpoint from per-node
        fragments (§4.2.4; Universal-Checkpointing shape).

        Waits up to ``timeout`` seconds (default 60) for in-flight updates
        — end to end, including the apply currently executing, so a wedged
        worker cannot hang recovery — then pulls each live node's fragment
        (concurrently; each apply-atomic) and assembles the full
        params/mu/nu trees. The wait is event-based (each worker signals
        when its queue drains), not a sleep-poll. Raises
        `ConsolidationTimeout` (lagging node ids, their owned buckets, and
        the partial checkpoint) if a live node is still behind at the
        deadline, and `ShadowNodeLoss` (dead node ids and EXACTLY their
        buckets as missing) if any node has been killed.
        """
        with _obs.get().tracer.span("shadow.consolidate", track="shadow"):
            return self._consolidate(timeout)

    def _consolidate(self, timeout: Optional[float]) -> dict:
        if self.async_mode:
            deadline = time.monotonic() + (60.0 if timeout is None else
                                           timeout)
            for i, (q, ev) in enumerate(zip(self._queues, self._drained)):
                if i in self.dead_nodes:
                    continue
                while self._pending(q):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not ev.wait(remaining):
                        break                  # deadline hit: node is lagging
                    if self._pending(q):
                        # stale signal (new work arrived since the worker
                        # drained): re-arm and wait for the next drain
                        ev.clear()
            lagging = [i for i, q in enumerate(self._queues)
                       if i not in self.dead_nodes and self._pending(q)]
            if lagging:
                raise ConsolidationTimeout(
                    lagging, self._gather(),
                    lagging_buckets={i: tuple(self.nodes[i].bucket_ids)
                                     for i in lagging})
        if self.dead_nodes:
            dead = sorted(self.dead_nodes)
            _obs.get().metrics.counter(
                "shadow_consolidate_missing_buckets_total",
                "Buckets unreachable at consolidate (dead owners)").inc(
                sum(len(self.nodes[n].bucket_ids) for n in dead))
            raise ShadowNodeLoss(
                dead, {n: tuple(self.nodes[n].bucket_ids) for n in dead},
                self._gather(),
                total=len(dead) == self.n_nodes,
                durable_hint=(self.durability.newest_durable()
                              if self.durability is not None else None))
        return self._gather()

    def _gather(self) -> dict:
        """Pull per-node fragments (concurrently — each node unpacks its own
        flat buffers, the distributed part of the gather) and assemble the
        tree from whatever nodes are alive."""
        live = [n for n in self.nodes if n.node_id not in self.dead_nodes]
        frags: dict[int, tuple] = {}

        def pull(node):
            frags[node.node_id] = node.snapshot()       # apply-atomic

        # one span from the calling thread (concurrent pulls would race on
        # the clock and break byte-identical ManualClock trace exports)
        with _obs.get().tracer.span("shadow.gather", track="shadow",
                                    args={"nodes": len(live)}):
            if len(live) > 1:
                threads = [threading.Thread(target=pull, args=(n,),
                                            daemon=True) for n in live]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            else:
                for n in live:
                    pull(n)
        params: dict = {}
        mu: dict = {}
        nu: dict = {}
        steps = []
        for nid in sorted(frags):
            p, m, v, step = frags[nid]
            params.update(p)
            mu.update(m)
            nu.update(v)
            steps.append(step)
        return {"params": params, "mu": mu, "nu": nu,
                "step": min(steps, default=0)}

    # backwards-compatible alias (pre-sharding name)
    _merge = _gather

    def stats(self) -> ShadowStats:
        count = sum(n.apply_count for n in self.nodes)
        total = sum(n.apply_total_s for n in self.nodes)
        per_node = [n.apply_total_s / n.apply_count if n.apply_count else 0.0
                    for n in self.nodes]
        live = [n for n in self.nodes if n.node_id not in self.dead_nodes]
        return ShadowStats(
            steps_applied=min((n.step for n in live), default=0),
            lag=self.train_step_seen - min((n.step for n in live),
                                           default=0),
            max_queue_depth=self.max_queue_depth,
            mean_apply_s=total / count if count else 0.0,
            max_apply_s=max((n.apply_max_s for n in self.nodes), default=0.0),
            per_node_apply_s=per_node,
            lag_waits=self.lag_waits,
            lag_wait_s=self.lag_wait_s_total,
            batched_applies=self.batched_applies,
            max_batch=self.max_batch)

    def shutdown(self):
        if self.durability is not None:
            self.durability.close()
        if self.async_mode:
            for q in self._queues:
                q.put(None)
            for t in self._workers:
                t.join(timeout=5)


def plan_shadow_nodes(layout: BucketLayout, opt: OptimizerConfig,
                      iter_time_s: float, trial_tree: dict,
                      max_nodes: int = 16) -> tuple[int, float]:
    """Paper §4.2.4: 'Before starting training, Checkmate profiles shadow
    nodes and configures the system for optimal performance.'

    Measures one full-tree optimizer apply on this host and returns the
    minimum node count whose per-node apply time fits inside an iteration,
    plus the measured single-node apply time.
    """
    cluster = ShadowCluster(layout, opt, n_nodes=1)
    zeros = {k: np.zeros(v.shape, np.float32) for k, v in trial_tree.items()}
    cluster.bootstrap(zeros, zeros, zeros, 0)
    grads = {k: np.ones(v.shape, np.float32) for k, v in trial_tree.items()}
    chan = InProcessChannel()
    chan.open(layout)

    def deliver(step):
        chan.send(StepEvent(step=step, grads=grads, lr=1e-3))
        for d in chan.poll():
            cluster.on_delivery(d)

    deliver(1)                                # warmup/compile
    t0 = time.perf_counter()
    deliver(2)
    t1 = time.perf_counter() - t0
    need = max(1, int(np.ceil(t1 / max(iter_time_s, 1e-9))))
    return min(need, max_nodes), t1
