"""Observability plane (docs/observability.md): registry semantics and
exposition, Perfetto-loadable trace export with monotonic per-track
timestamps, deterministic golden traces under ManualClock, near-zero-cost
disabled hot paths, bit-exact stall attribution from channel send parts
through the checkpointer ledger, per-link PFC accounting, and the
``python -m repro.obs`` CLI."""
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import ManualClock, MetricsRegistry, Tracer, diff_snapshots
from repro.obs.metrics import NULL_INSTRUMENT
from repro.obs.trace import FABRIC_PID, HOST_PID, NULL_SPAN


# -- metrics registry ---------------------------------------------------------

def test_counter_gauge_histogram_with_labels():
    reg = MetricsRegistry()
    reg.counter("sends", "help text").inc(2, channel="a")
    reg.counter("sends").inc(3, channel="a")
    reg.counter("sends").inc(1, channel="b")
    reg.gauge("lag").set(4)
    reg.histogram("apply_s").observe(0.002, node=0)
    reg.histogram("apply_s").observe(0.2, node=0)

    snap = reg.snapshot()["metrics"]
    by_label = {s["labels"]["channel"]: s["value"]
                for s in snap["sends"]["samples"]}
    assert by_label == {"a": 5, "b": 1}
    assert snap["sends"]["type"] == "counter"
    assert snap["sends"]["help"] == "help text"
    assert snap["lag"]["samples"][0]["value"] == 4
    h = snap["apply_s"]["samples"][0]
    assert h["count"] == 2 and h["max"] == 0.2
    assert h["sum"] == pytest.approx(0.202)
    assert h["buckets"]["+Inf"] == 2              # cumulative


def test_metric_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("sends", "Gradient sends").inc(5, channel="inprocess")
    reg.histogram("apply_s", bounds=(0.01, 0.1)).observe(0.05)
    text = reg.to_prometheus()
    assert "# HELP sends Gradient sends" in text
    assert "# TYPE sends counter" in text
    assert 'sends{channel="inprocess"} 5' in text
    assert 'apply_s_bucket{le="0.1"} 1' in text
    assert 'apply_s_bucket{le="+Inf"} 1' in text
    assert "apply_s_count 1" in text


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    # accessors hand back one shared null instrument: no allocation, no state
    assert reg.counter("a") is NULL_INSTRUMENT
    assert reg.gauge("b") is NULL_INSTRUMENT
    assert reg.histogram("c") is NULL_INSTRUMENT
    reg.counter("a").inc(10)
    assert reg.snapshot() == {"metrics": {}}


def test_diff_snapshots():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("sends").inc(1, channel="x")
    b.counter("sends").inc(4, channel="x")
    b.gauge("lag").set(2)
    rows = diff_snapshots(a.snapshot(), b.snapshot())
    assert {(r["metric"], r["before"], r["after"]) for r in rows} == {
        ("sends", 1, 4), ("lag", None, 2)}


# -- tracer -------------------------------------------------------------------

def _small_trace():
    tr = Tracer(clock=ManualClock(0.0))
    with tr.span("step.compute", args={"step": 1}):
        with tr.span("channel.send", track="train"):
            pass
    tr.instant("recovery.resume", track="recovery")
    tr.fabric_span("allgather step1", 0.0, 30e-6, track="fabric")
    tr.fabric_span("g0c0r0", 1e-6, 2e-6, track="shadow0.rx")
    tr.fabric_advance(30e-6)
    tr.fabric_span("allgather step2", 0.0, 30e-6, track="fabric")
    return tr


def test_export_is_perfetto_loadable():
    doc = _small_trace().export()
    # must be a JSON-serializable trace_event object form
    doc2 = json.loads(json.dumps(doc))
    assert doc2["displayTimeUnit"] == "ms"
    evs = doc2["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X"}
    meta = [e for e in evs if e["ph"] == "M"]
    names = {(e["pid"], e["name"], e["args"]["name"]) for e in meta}
    assert (HOST_PID, "process_name", "host (wall clock)") in names
    assert (FABRIC_PID, "process_name", "fabric (simulated time)") in names
    # every X event's track has thread_name metadata
    tids = {(e["pid"], e["tid"]) for e in evs if e["ph"] == "X"}
    assert tids <= {(e["pid"], e["tid"]) for e in meta
                    if e["name"] == "thread_name"}


def test_timestamps_monotonic_nonnegative_per_track():
    evs = _small_trace().events()
    seen = {}
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        key = (e["pid"], e["tid"])
        assert e["ts"] >= seen.get(key, 0.0)      # ordered within a track
        seen[key] = e["ts"]
    # fabric_advance laid step2's allgather after step1's
    ag = [e for e in evs if e["name"].startswith("allgather")]
    assert ag[1]["ts"] >= ag[0]["ts"] + ag[0]["dur"]


def test_ring_buffer_keeps_trailing_window():
    tr = Tracer(clock=ManualClock(0.0), maxlen=8)
    for i in range(50):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8
    assert evs[-1]["name"] == "e49"


def test_manual_clock_golden_scenario_trace_is_deterministic():
    """Fixed scenario + logical clock => byte-identical trace export."""
    from repro.harness import GOLDEN, run_scenario

    def one_run():
        with obs.enabled_session(clock=ManualClock(0.0)) as ob:
            result = run_scenario(GOLDEN["packetized-rail-clean"])
            assert result.passed
            return json.dumps(ob.tracer.export(), sort_keys=True)

    assert one_run() == one_run()


# -- the profiler's own clock ---------------------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session on the CPU: an enabled tracer's spans (one
    nested in the other), an instant, and a disabled tracer's span. Returns
    the tracer's events and the XPlane host plane's {name: [duration_s]}."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tr, off = Tracer(), Tracer(enabled=False)
    out = tmp_path_factory.mktemp("xplane")
    with jax.profiler.trace(str(out)):
        with tr.span("obs.mirrored"):
            time.sleep(0.02)
            with tr.span("obs.child"):
                time.sleep(0.005)
        tr.instant("obs.instant")
        with off.span("obs.disabled"):
            time.sleep(0.005)
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    return {e["name"]: e for e in tr.events()}, host


def test_span_is_mirrored_into_the_profilers_host_plane(profiled):
    events, host = profiled
    for name in ("obs.mirrored", "obs.child"):
        assert len(host.get(name, [])) == 1, name       # its exact name
        assert host[name][0] == pytest.approx(events[name]["dur"] * 1e-6,
                                              abs=1e-3)


def test_instant_leaves_no_annotation(profiled):
    events, host = profiled
    assert "obs.instant" in events
    assert "obs.instant" not in host


def test_disabled_tracer_opens_no_annotation(profiled):
    _, host = profiled
    assert "obs.disabled" not in host


def test_spans_export_their_parents_name():
    import threading
    tr = Tracer(clock=ManualClock(0.0))
    with tr.span("step.compute", args={"step": 1}):
        with tr.span("step.wait", args={"step": 1}):
            pass

        def other_thread():                 # a stack of its own
            with tr.span("shadow.apply"):
                pass
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    with tr.span("loop.hook"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    assert ev["step.wait"]["args"] == {"step": 1, "parent": "step.compute"}
    assert ev["step.compute"]["args"] == {"step": 1}
    assert "args" not in ev["shadow.apply"]
    assert "args" not in ev["loop.hook"]


# -- the training loop's spans ----------------------------------------------------

LOOP_SPANS = ("data.batch", "data.put", "step.dispatch", "step.wait",
              "step.readback", "step.free", "loop.hook")


@pytest.fixture(scope="module")
def traced_train():
    """Three steps of a tiny model through the in-process channel into a
    two-node shadow, under an enabled session."""
    import jax
    import repro.configs as C
    from repro.core.buckets import layout_for_tree
    from repro.core.channel import InProcessChannel
    from repro.dist.sharding import ShardingRules, make_smoke_mesh
    from repro.optim import OptimizerConfig
    from repro.train.loop import train
    from repro.train.step import make_train_state
    cfg = C.get("llama3.2-3b").reduced()
    rules = ShardingRules(make_smoke_mesh())
    s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    layout = layout_for_tree(s0.params)
    hooked = []
    with obs.enabled_session() as ob:
        _, stats = train(cfg, rules, steps=3, batch=2, seq=16,
                         opt=OptimizerConfig(lr=1e-3), state=s0,
                         channel=InProcessChannel(),
                         step_hook=lambda step, *_: hooked.append(step))
        stats.checkpointer.shadow.shutdown()
        snap = ob.metrics.snapshot()["metrics"]
    return ob.tracer.events(), snap, stats, layout, hooked


def test_every_loop_span_once_per_step(traced_train):
    events, _, stats, _, hooked = traced_train
    assert hooked == [1, 2, 3]
    for name in LOOP_SPANS:
        steps = sorted(e["args"]["step"] for e in events if e["name"] == name)
        assert steps == [1, 2, 3], name
    parent = {e["name"]: e["args"].get("parent") for e in events
              if e["name"] in LOOP_SPANS}
    assert parent["step.dispatch"] == parent["step.wait"] == "step.compute"
    assert parent["data.batch"] is None and parent["loop.hook"] is None


def test_copies_carry_the_layouts_gradient_bytes(traced_train):
    events, snap, _, layout, _ = traced_train
    freed = [e["args"]["step"] for e in events if e["name"] == "capture.free"]
    assert freed == [2, 3]              # the last step's copy, given back
    for name in ("capture.d2h", "bucket.pack"):
        got = [e["args"]["bytes"] for e in events if e["name"] == name]
        assert got == [layout.total_bytes] * 3, name
    packs = [e["args"]["buckets"] for e in events if e["name"] == "bucket.pack"]
    assert packs == [len(layout.buckets)] * 3
    # the sync shadow gives each step's wire buffers back: the next reuses
    reused = [e["args"]["reused"] for e in events if e["name"] == "bucket.pack"]
    assert reused == [0] + [layout.total_bytes] * 2
    for counter in ("capture_bytes_total", "channel_pack_bytes_total"):
        total = sum(s["value"] for s in snap[counter]["samples"])
        assert total == 3 * layout.total_bytes, counter
    total = sum(s["value"] for s in
                snap["channel_pack_reused_bytes_total"]["samples"])
    assert total == 2 * layout.total_bytes


def test_shadow_bootstrap_spans_its_copy_and_each_install(traced_train):
    events, _, stats, layout, _ = traced_train
    boot = [e for e in events if e["name"].startswith("shadow.bootstrap")]
    kinds = {e["name"]: e for e in boot}
    assert set(kinds) == {"shadow.bootstrap", "shadow.bootstrap.d2h",
                          "shadow.bootstrap.install"}
    d2h = kinds["shadow.bootstrap.d2h"]
    assert d2h["args"]["parent"] == "shadow.bootstrap"
    assert d2h["args"]["bytes"] == 3 * layout.total_bytes   # params, mu, nu
    nodes = sorted(e["args"]["node"] for e in boot
                   if e["name"] == "shadow.bootstrap.install")
    assert nodes == list(range(stats.checkpointer.shadow.n_nodes))


def test_tokens_per_s_runs_from_the_first_steps_end_to_the_last():
    from repro.train.loop import LoopStats
    stats = LoopStats(tokens_per_step=100, step_ends=[10.0, 10.5, 11.0, 12.0])
    assert stats.step_s == pytest.approx(2.0 / 3)
    assert stats.tokens_per_s == pytest.approx(150.0)
    assert LoopStats(tokens_per_step=100, step_ends=[1.0]).tokens_per_s == 0.0


# -- disabled hot paths -------------------------------------------------------

def test_disabled_hot_path_is_noop_and_cheap():
    ob = obs.Observability.disabled()
    assert not ob.enabled
    # the guarantee: shared singletons, zero per-call allocation of state
    assert ob.tracer.span("channel.send") is NULL_SPAN
    assert ob.metrics.counter("sends") is NULL_INSTRUMENT
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with ob.tracer.span("channel.send", args={"step": 1}):
            pass
        ob.metrics.counter("sends").inc(1, channel="x")
    dt = time.perf_counter() - t0
    # generous CI-safe bound: ~50us/iteration would still pass; the real
    # cost is ~1us. Catches accidental work (dict churn, time syscalls)
    # sneaking into the disabled path.
    assert dt < 1.0, f"disabled hot path cost {dt / n * 1e6:.1f}us/iter"
    assert ob.metrics.snapshot() == {"metrics": {}}
    assert ob.tracer.events() == []


# -- stall attribution: channel send parts ------------------------------------

def _tree(n=4):
    rng = np.random.default_rng(0)
    return {f"l{i}.w": rng.standard_normal((8, 16)).astype(np.float32)
            for i in range(n)}


def _in_order_sum(parts: dict) -> float:
    total = 0.0
    for v in parts.values():
        total += v
    return total


@pytest.mark.parametrize("kind", ["inprocess", "packetized", "compressed"])
def test_send_parts_sum_bit_exactly_to_reported_stall(kind):
    from repro.core.buckets import layout_for_tree
    from repro.core.channel import (CompressedChannel, InProcessChannel,
                                    PacketizedChannel, StepEvent)
    tree = _tree()
    layout = layout_for_tree(tree)
    chan = {"inprocess": InProcessChannel,
            "packetized": lambda: PacketizedChannel(n_shadow_nodes=2),
            "compressed": lambda: CompressedChannel(InProcessChannel()),
            }[kind]()
    chan.open(layout)
    for step in (1, 2):
        reported = chan.send(StepEvent(step=step, grads=tree, lr=1e-3))
        parts = chan.last_send_parts
        assert parts, "every send must set last_send_parts"
        assert _in_order_sum(parts) == reported        # bit-exact, not approx
    if kind == "packetized":
        assert parts == {"send": 0.0}      # the paper's zero-overhead claim
    if kind == "compressed":
        assert "quantize" in parts and "send" in parts
    chan.close()


# -- stall attribution: checkpointer ledger -----------------------------------

def _checkmate(channel=None, n=4):
    from repro.core.buckets import layout_for_tree
    from repro.core.checkpoint import CheckmateCheckpointer
    from repro.core.shadow import ShadowCluster
    from repro.optim import OptimizerConfig
    tree = _tree(n)
    layout = layout_for_tree(tree)
    zeros = {k: np.zeros_like(v) for k, v in tree.items()}
    shadow = ShadowCluster(layout, OptimizerConfig(name="sgd", lr=1e-3),
                           n_nodes=2)
    shadow.bootstrap(tree, zeros, zeros, 0)
    return CheckmateCheckpointer(shadow, channel=channel), tree, zeros


def test_stall_total_is_in_order_ledger_sum():
    from repro.core.channel import StepEvent
    ck, tree, _ = _checkmate()
    for step in (1, 2, 3):
        ck.on_step(StepEvent(step=step, grads=tree, lr=1e-3))
    ck.restore()                                   # books consolidate-wait
    assert set(ck.stall_stages) == {"send", "inline-apply",
                                    "consolidate-wait"}
    assert ck.stall_total == _in_order_sum(ck.stall_stages)
    assert all(v >= 0.0 for v in ck.stall_stages.values())


def test_resync_and_gated_steps_attributed():
    from repro.core.channel import PacketizedChannel, StepEvent
    chan = PacketizedChannel(n_shadow_nodes=2, failures_at={1: "capture"})
    ck, tree, zeros = _checkmate(channel=chan)
    # step 1: capture lost -> gated, books nothing
    assert ck.on_step(StepEvent(step=1, grads=tree, lr=1e-3)) == 0.0
    assert ck.skipped_captures == 1 and ck.stall_stages == {}
    # step 2 carries state_fn -> full-state resync, charged to "resync"
    snap = {"params": tree, "mu": zeros, "nu": zeros, "step": 2}
    stall = ck.on_step(StepEvent(step=2, grads=tree, lr=1e-3,
                                 state_fn=lambda: snap))
    assert ck.resyncs == [2]
    assert set(ck.stall_stages) == {"resync"}
    assert ck.stall_stages["resync"] == stall
    assert ck.stall_total == _in_order_sum(ck.stall_stages)


def test_copy_persist_baseline_books_single_stage():
    from repro.core.channel import StepEvent
    from repro.core.checkpoint import SyncCheckpointer
    tree = _tree()
    zeros = {k: np.zeros_like(v) for k, v in tree.items()}
    ck = SyncCheckpointer(freq=1)
    snap = {"params": tree, "mu": zeros, "nu": zeros, "step": 1}
    ck.on_step(StepEvent(step=1, state_fn=lambda: snap))
    assert set(ck.stall_stages) == {"copy-persist"}
    assert ck.stall_total == ck.stall_stages["copy-persist"]


def test_stall_report_and_publish():
    from repro.core.channel import StepEvent
    from repro.obs.stalls import format_stall_report, stall_attribution
    ck, tree, _ = _checkmate()
    ck.on_step(StepEvent(step=1, grads=tree, lr=1e-3))
    parts = stall_attribution(ck)
    assert sum(parts.values()) == ck.stall_total
    report = format_stall_report(ck)
    assert "inline-apply" in report and "total" in report
    reg = MetricsRegistry()
    from repro.obs.stalls import publish_stalls
    publish_stalls(reg, ck)
    fam = reg.snapshot()["metrics"]["checkpoint_stall_seconds_total"]
    assert {s["labels"]["stage"] for s in fam["samples"]} == set(parts)


# -- per-link PFC -------------------------------------------------------------

def test_per_link_pfc_pause_accounting():
    from repro.net.simulator import PfcConfig, simulate_fabric
    r = simulate_fabric(2, 8, 8 * 65536, n_shadow_nodes=2, ranks_per_leaf=4,
                        replication_factor=8,
                        pfc=PfcConfig(capacity_bytes=32768, xoff_frac=0.5,
                                      xon_frac=0.25))
    assert r.pfc_pauses > 0                       # congestion actually paused
    assert r.link_pfc, "paused links must be reported individually"
    for link, st in r.link_pfc.items():
        assert "->" in link
        assert st["pauses"] > 0 and st["pause_s"] >= 0.0
    # the aggregate is exactly the per-link decomposition
    assert sum(st["pause_s"] for st in r.link_pfc.values()) == r.pfc_pause_s


def test_per_link_pfc_published_as_labeled_gauge():
    from repro.core.channel import FabricTotals
    from repro.net.simulator import PfcConfig, simulate_fabric
    from repro.obs.publish import publish_channel
    r = simulate_fabric(2, 8, 8 * 65536, n_shadow_nodes=2, ranks_per_leaf=4,
                        replication_factor=8,
                        pfc=PfcConfig(capacity_bytes=32768, xoff_frac=0.5,
                                      xon_frac=0.25))
    totals = FabricTotals()
    totals.absorb(r, 8 * 65536)

    class FakeChannel:
        name = "packetized"
    FakeChannel.totals = totals

    reg = MetricsRegistry()
    publish_channel(reg, FakeChannel())
    snap = reg.snapshot()["metrics"]
    samples = snap["fabric_link_pfc_pause_seconds"]["samples"]
    assert {s["labels"]["link"] for s in samples} == set(r.link_pfc)
    total = snap["fabric_pfc_pause_seconds_total"]["samples"][0]["value"]
    assert total == pytest.approx(r.pfc_pause_s)


# -- harness + session integration --------------------------------------------

def test_run_scenario_always_carries_trailing_trace_window():
    from repro.harness import GOLDEN, run_scenario
    assert not obs.get().enabled                  # ambient plane is the no-op
    result = run_scenario(GOLDEN["inprocess-clean"])
    assert result.trace_export is not None
    names = {e.get("name") for e in result.trace_export["traceEvents"]}
    assert "checkpoint.on_step" in names and "channel.send" in names
    assert not obs.get().enabled                  # runner restored the plane


def test_enabled_session_scopes_and_restores():
    with obs.enabled_session() as ob:
        assert obs.get() is ob and ob.enabled
        with ob.tracer.span("step.compute"):
            pass
        ob.metrics.counter("train_steps_total").inc()
    assert not obs.get().enabled


def test_shadow_apply_observed_under_session():
    from repro.core.channel import StepEvent
    with obs.enabled_session() as ob:
        ck, tree, _ = _checkmate()
        ck.on_step(StepEvent(step=1, grads=tree, lr=1e-3))
        snap = ob.metrics.snapshot()["metrics"]
        names = {e.get("name") for e in ob.tracer.events()}
    h = snap["shadow_apply_seconds"]["samples"]
    assert sum(s["count"] for s in h) >= 1
    assert "shadow.apply" in names


# -- CLI ----------------------------------------------------------------------

def test_cli_trace_covers_send_fabric_apply_for_every_step(tmp_path):
    """Acceptance: `repro.obs trace --scenario <golden>` emits send ->
    fabric -> shadow-apply spans for every non-gated step."""
    from repro.harness import GOLDEN
    from repro.obs.__main__ import main
    out = tmp_path / "t.trace.json"
    mout = tmp_path / "m.json"
    rc = main(["trace", "--scenario", "packetized-rail-clean",
               "--out", str(out), "--metrics-out", str(mout)])
    assert rc == 0
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    steps = range(1, GOLDEN["packetized-rail-clean"].steps + 1)

    def steps_of(name):
        return {e.get("args", {}).get("step") for e in evs
                if e["name"] == name}

    assert set(steps) <= steps_of("channel.send")         # send
    ag = {e.get("args", {}).get("step") for e in evs
          if e["name"].startswith("allgather step")}      # fabric domain
    assert set(steps) <= ag
    assert any(e["name"] == "shadow.apply" for e in evs)  # shadow apply
    assert {e["pid"] for e in evs} == {HOST_PID, FABRIC_PID}
    # the metrics snapshot rode along
    snap = json.loads(mout.read_text())
    assert snap["metrics"]["checkpoints_total"]["samples"][0]["value"] == 5


def test_cli_diff(tmp_path, capsys):
    from repro.obs.__main__ import main
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("sends").inc(1)
    b.counter("sends").inc(7)
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    a.write_json(pa)
    b.write_json(pb)
    assert main(["diff", str(pa), str(pb)]) == 0
    out = capsys.readouterr().out
    assert "sends" in out and "1 -> 7" in out


def test_cli_rejects_unknown_scenario(tmp_path):
    from repro.obs.__main__ import main
    with pytest.raises(SystemExit):
        main(["trace", "--scenario", "no-such-scenario"])
