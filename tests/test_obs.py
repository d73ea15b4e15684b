"""Flight-recorder coverage: tracer ring/sampling/export, histogram metric
semantics, the controller decision audit, request-chain validation, the
Chrome-trace exporter, fleet_top aggregation, and the TelemetryBus edge
cases the EWMA/window design relies on.

The headline drill (slow lane): the durable-KV recovery fleet under
mid-decode kills and a preemption notice must produce an audit log whose
every mode switch is explainable from its recorded signals, request chains
that stay contiguous across replica migrations, and a valid Chrome-trace
timeline covering >= 99% of completed requests.
"""
import json
import os
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import policy
from repro.fleet.telemetry import TTFT_WINDOW, TelemetryBus
from repro.obs import (
    CAPACITY_OPTIMIZED,
    COST_OPTIMIZED,
    Counter,
    DecisionRecord,
    Histogram,
    MetricsRegistry,
    Tracer,
    log_buckets,
    request_chains,
    validate_chain,
)
from repro.obs.trace import load_jsonl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, ROOT)
import fleet_top  # noqa: E402
import trace_export  # noqa: E402
from benchmarks.chip import trace_reduce  # noqa: E402


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_ring_bounds_memory():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.event("e", t=float(i), cat="req", i=i)
    assert len(tr.events) == 4
    assert tr.emitted == 10
    assert tr.dropped == 6
    assert [e["i"] for e in tr.events] == [6, 7, 8, 9]   # oldest fell off


def test_tracer_sampling_decimates_only_sampled_events():
    tr = Tracer(sample=0.25)
    for i in range(100):
        tr.event("hf", t=float(i), sampled=True)
        tr.event("lifecycle", t=float(i))
    hf = tr.select(name="hf")
    assert len(hf) == 25                      # deterministic stride of 4
    assert len(tr.select(name="lifecycle")) == 100
    assert tr.sampled_out == 75


def test_tracer_disabled_records_nothing():
    tr = Tracer.disabled()
    assert tr.event("x", t=0.0) is False
    with tr.begin("span", t=0.0) as sp:
        pass
    assert len(tr.events) == 0 and tr.emitted == 0


def test_tracer_clock_and_span_duration():
    now = {"t": 5.0}
    tr = Tracer(clock=lambda: now["t"])
    sp = tr.begin("work", cat="engine", replica="r1")
    now["t"] = 7.5
    sp.end()
    sp.end()                                  # double-end is a no-op
    (ev,) = tr.to_list()
    assert ev["t"] == 5.0 and ev["dur"] == 2.5 and ev["replica"] == "r1"
    assert tr.event("later") and tr.to_list()[-1]["t"] == 7.5


def test_span_carries_wall_clock_and_parent():
    now = {"t": 1.0}
    tr = Tracer(clock=lambda: now["t"])
    w_before = time.perf_counter()
    with tr.begin("outer", replica="r1"):
        tr.event("inside")
        with tr.begin("inner", cat="engine"):
            time.sleep(0.002)
        now["t"] = 2.0
    w_after = time.perf_counter()
    ev = {e["name"]: e for e in tr.to_list()}
    outer, inner, inside = ev["outer"], ev["inner"], ev["inside"]
    # every event carries the wall clock beside the owner's clock
    assert w_before <= outer["w"] <= inside["w"] <= inner["w"]
    # a span: t/dur on the owner's clock, w/wall_s on the wall clock
    assert (outer["t"], outer["dur"]) == (1.0, 1.0)
    assert (inner["t"], inner["dur"]) == (1.0, 0.0)
    assert inner["wall_s"] >= 0.002
    assert inner["w"] + inner["wall_s"] <= outer["w"] + outer["wall_s"] <= w_after
    # the span open when one began is its parent
    assert outer["parent"] is None and inner["parent"] == "outer"
    assert outer["replica"] == "r1"
    assert "parent" not in inside


def test_sibling_spans_share_a_parent_and_the_stack_unwinds():
    tr = Tracer()
    with tr.begin("fleet.tick"):
        for name in ("fleet.intake", "fleet.control", "fleet.dispatch"):
            with tr.begin(name):
                pass
        with tr.begin("engine.pump", cat="engine"):
            with tr.begin("pump.admit", cat="engine"):
                pass
    with tr.begin("fleet.tick"):
        pass
    parents = [(e["name"], e["parent"]) for e in tr.to_list()]
    assert parents == [
        ("fleet.intake", "fleet.tick"), ("fleet.control", "fleet.tick"),
        ("fleet.dispatch", "fleet.tick"), ("pump.admit", "engine.pump"),
        ("engine.pump", "fleet.tick"), ("fleet.tick", None),
        ("fleet.tick", None)]


def test_sampled_span_keeps_or_drops_its_children_whole():
    tr = Tracer(sample=0.5)
    walls = []
    for i in range(4):
        with tr.begin("engine.pump", t=float(i), cat="engine",
                      sampled=True) as sp:
            for name in ("pump.admit", "pump.decode"):
                with tr.begin(name, cat="engine", sampled=True):
                    pass
        walls.append(sp.wall_s)
    assert len(tr.select(name="engine.pump")) == 2
    assert len(tr.select(name="pump.admit")) == 2
    assert len(tr.select(name="pump.decode")) == 2
    assert tr.sampled_out == 2                # one stride per pump tree
    assert all(w > 0 for w in walls)          # timed even when dropped


def test_span_unwound_by_an_exception_is_dropped():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.begin("engine.pump", cat="engine"):
            tr.begin("pump.prefill", cat="engine")     # never ended
            raise RuntimeError("pump failed")
    with tr.begin("fleet.tick"):
        pass
    (ev,) = tr.to_list()
    assert ev["name"] == "fleet.tick" and ev["parent"] is None


def test_disabled_tracer_spans_only_time_themselves():
    tr = Tracer.disabled()
    with tr.begin("outer") as outer:
        with tr.begin("inner", sampled=True) as inner:
            time.sleep(0.001)
    assert inner.wall_s >= 0.001 and outer.wall_s >= inner.wall_s
    assert inner.parent is None and not inner.recorded
    assert len(tr.events) == 0 and tr.emitted == 0


def test_spans_reach_the_profiler_trace_nested(tmp_path):
    """A recorded span holds a profiler annotation of its name: the xplane
    shows the pair on the host plane with its nesting intact."""
    tr = Tracer()
    jax.block_until_ready(jax.numpy.ones(4) * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.begin("fleet.tick"):
            with tr.begin("engine.pump", cat="engine"):
                jax.block_until_ready(jax.numpy.ones(4) * 2)
    finally:
        jax.profiler.stop_trace()
    got = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                            ("fleet.tick", "engine.pump")).spans
    (tick,) = [sp for sp in got if sp[0] == "fleet.tick"]
    (pump,) = [sp for sp in got if sp[0] == "engine.pump"]
    assert tick[1] <= pump[1] < pump[2] <= tick[2]
    assert {e["name"]: e["parent"] for e in tr.to_list()} == {
        "engine.pump": "fleet.tick", "fleet.tick": None}


def test_tracer_jsonl_roundtrip_with_numpy(tmp_path):
    tr = Tracer()
    tr.event("e", t=1.0, cat="ctl", pool=np.array([1, 2]),
             demand=np.float64(3.5), tiers=("a", "b"))
    path = str(tmp_path / "trace.jsonl")
    assert tr.dump_jsonl(path) == 1
    (ev,) = load_jsonl(path)
    assert ev["pool"] == [1, 2] and ev["demand"] == 3.5
    assert ev["tiers"] == ["a", "b"]


def test_tracer_rejects_bad_params():
    with pytest.raises(ValueError):
        Tracer(capacity=0)
    with pytest.raises(ValueError):
        Tracer(sample=0.0)
    with pytest.raises(ValueError):
        Tracer(sample=1.5)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_log_buckets_cover_range_with_stable_edges():
    edges = log_buckets(1e-3, 1.0, per_decade=3)
    assert edges[0] == 1e-3 and edges[-1] >= 1.0
    assert edges == tuple(sorted(edges))
    # stable short-decimal rounding: re-deriving gives identical labels
    assert edges == log_buckets(1e-3, 1.0, per_decade=3)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


def test_histogram_le_bucket_boundaries():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    h.observe(1.0)            # exactly on an edge -> that edge's bucket
    h.observe(0.5)            # below the first edge -> first bucket
    h.observe(10.0)
    h.observe(10.0001)        # just past the edge -> next bucket
    h.observe(1000.0)         # past the last edge -> +Inf overflow
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(1021.5001)


def test_histogram_percentiles_saturate_at_last_edge():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    assert h.percentile(99.0) == 0.0          # empty
    for _ in range(99):
        h.observe(0.5)
    h.observe(5000.0)                          # overflow observation
    assert h.percentile(50.0) == 1.0           # upper-edge rule
    assert h.percentile(100.0) == 100.0        # saturates, never invents
    assert h.mean == pytest.approx((99 * 0.5 + 5000.0) / 100)


def test_counter_is_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_exposition_and_kind_guard():
    reg = MetricsRegistry()
    fam = reg.counter("req_total", "requests", labels=("tier",))
    fam.labels("cheap").inc(3)
    fam.labels("premium").inc()
    reg.gauge("queue_depth", "depth").set(7)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.exposition()
    assert '# TYPE req_total counter' in text
    assert 'req_total{tier="cheap"} 3' in text
    assert 'queue_depth 7' in text
    # cumulative le buckets + overflow + sum/count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert 'lat_seconds_count 3' in text
    # redeclare same kind returns the family; kind mismatch raises
    assert reg.counter("req_total") is fam
    with pytest.raises(ValueError):
        reg.gauge("req_total")
    with pytest.raises(ValueError):
        fam.labels()                           # missing label value


# ---------------------------------------------------------------------------
# Decision audit
# ---------------------------------------------------------------------------


def _decision(**kw):
    base = dict(
        t=3.0, prev_mode=COST_OPTIMIZED, mode=CAPACITY_OPTIMIZED,
        switched=True, demand=10.0, tiers=("cheap", "premium"),
        pool=(4, 2), requested=(2, 1), measured_t_max=(1.0, 2.0),
        tentative=(8, 1), cap_violated=True, supply_possible=8.0,
        hold_supply=4.0, hysteresis_margin=0.25,
    )
    base.update(kw)
    return DecisionRecord(**base)


def test_audit_constants_mirror_policy():
    assert COST_OPTIMIZED == policy.COST_OPTIMIZED
    assert CAPACITY_OPTIMIZED == policy.CAPACITY_OPTIMIZED


def test_decision_record_explains_each_branch():
    # capacity via Eq.(3) violation
    assert _decision().explains()
    # capacity via raw supply shortfall
    assert _decision(cap_violated=False, supply_possible=8.0).explains()
    # hysteresis hold: supply recovered but margin not met
    assert _decision(prev_mode=CAPACITY_OPTIMIZED, cap_violated=False,
                     supply_possible=11.0, hold_supply=11.0,
                     switched=False).explains()
    # cost: margin met
    assert _decision(prev_mode=CAPACITY_OPTIMIZED, mode=COST_OPTIMIZED,
                     cap_violated=False, supply_possible=14.0,
                     hold_supply=13.0).explains()
    # a record whose signals CONTRADICT its mode is flagged
    assert not _decision(mode=COST_OPTIMIZED, switched=False).explains()


def test_decision_record_reason_and_signals():
    rec = _decision()
    assert "cost allocation wants" in rec.reason()
    sig = rec.signals()
    assert sig["pool"] == (4, 2) and sig["cap_violated"] is True
    assert "capacity: supply" in _decision(cap_violated=False).reason()
    assert "hysteresis hold" in _decision(
        cap_violated=False, supply_possible=20.0).reason()
    assert "cost:" in _decision(mode=COST_OPTIMIZED).reason()


# ---------------------------------------------------------------------------
# Request chains
# ---------------------------------------------------------------------------


def _ev(name, t, **args):
    return {"t": t, "name": name, "cat": "req", **args}


def test_request_chains_groups_and_sorts():
    events = [
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.queued", 0.0, rid=1),
        _ev("req.queued", 0.5, rid=2),
        {"t": 0.2, "name": "ctl.scale", "cat": "ctl"},   # not a req event
    ]
    chains = request_chains(events)
    assert set(chains) == {1, 2}
    assert [e["name"] for e in chains[1]] == ["req.queued", "req.dispatched"]


def test_validate_chain_accepts_contiguous_migration():
    chain = [
        _ev("req.queued", 0.0, rid=7),
        _ev("req.dispatched", 1.0, rid=7, replica="a"),
        _ev("req.first_token", 2.0, rid=7, replica="a"),
        _ev("req.requeued", 3.0, rid=7, replica="a"),
        _ev("req.dispatched", 4.0, rid=7, replica="b"),
        _ev("req.completed", 5.0, rid=7, replica="b"),
    ]
    assert validate_chain(chain) == []


def test_validate_chain_flags_violations():
    # re-dispatch without a requeue explaining why it left replica a
    bad = [
        _ev("req.queued", 0.0, rid=1),
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.dispatched", 2.0, rid=1, replica="b"),
    ]
    assert any("without a req.requeued" in p for p in validate_chain(bad))
    # requeued from a replica it was never dispatched to
    bad = [
        _ev("req.queued", 0.0, rid=1),
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.requeued", 2.0, rid=1, replica="z"),
    ]
    assert any("never dispatched there" in p for p in validate_chain(bad))
    # events after a terminal state
    bad = [
        _ev("req.queued", 0.0, rid=1),
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.completed", 2.0, rid=1, replica="a"),
        _ev("req.dispatched", 3.0, rid=1, replica="b"),
    ]
    assert any("after terminal" in p for p in validate_chain(bad))
    # completed on a replica the trace never dispatched it to
    bad = [
        _ev("req.queued", 0.0, rid=1),
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.completed", 2.0, rid=1, replica="z"),
    ]
    assert any("dispatched to" in p for p in validate_chain(bad))
    # missing / duplicated queued
    assert any("req.queued" in p for p in validate_chain(
        [_ev("req.dispatched", 1.0, rid=1, replica="a")]))


def test_validate_chain_hedge_counts_as_dispatch():
    chain = [
        _ev("req.queued", 0.0, rid=1),
        _ev("req.dispatched", 1.0, rid=1, replica="a"),
        _ev("req.hedged", 1.0, rid=1, replica="b"),
        _ev("req.completed", 2.0, rid=1, replica="b"),   # hedge twin won
    ]
    assert validate_chain(chain) == []


# ---------------------------------------------------------------------------
# TelemetryBus edge cases
# ---------------------------------------------------------------------------


def _pump_report(occupancy=0.5, wall_s=0.1, useful_tokens=10, completed=1):
    return SimpleNamespace(occupancy=occupancy, wall_s=wall_s,
                           useful_tokens=useful_tokens,
                           completed={i: None for i in range(completed)})


def test_idle_tier_ewma_does_not_decay():
    bus = TelemetryBus(["t"], alpha=0.5)
    bus.record_ready("t", 1)
    bus.record_pump("t", "t/r1", _pump_report(completed=4), queue_depth=0)
    bus.roll(1.0)
    rate = bus.tier_rate["t"].get()
    assert rate > 0
    for _ in range(50):                        # idle ticks: no pumps at all
        bus.roll(1.0)
    assert bus.tier_rate["t"].get() == rate    # capacity estimate held


def test_ttft_window_evicts_at_maxlen():
    bus = TelemetryBus(["t"])
    for i in range(TTFT_WINDOW + 100):
        bus.record_completion("t", "t/r1", ttft_s=float(i), tpot_s=0.01,
                              tokens=2)
    win = bus._ttft_window["t"]
    assert len(win) == TTFT_WINDOW
    assert min(win) == 100.0                   # oldest 100 evicted
    assert bus.ttft_p99("t") >= 100.0


def test_tpot_p99_window_and_snapshot_key():
    bus = TelemetryBus(["t"])
    assert bus.tpot_p99("t") == 0.0            # empty until a completion
    # single-token completions must not contaminate the TPOT window
    bus.record_completion("t", "t/r1", ttft_s=0.1, tpot_s=99.0, tokens=1)
    assert bus.tpot_p99("t") == 0.0
    for i in range(100):
        bus.record_completion("t", "t/r1", ttft_s=0.1,
                              tpot_s=0.01 * (i + 1), tokens=4)
    p99 = bus.tpot_p99("t")
    assert 0.9 <= p99 <= 1.0
    snap = bus.snapshot()["t"]
    assert snap["tpot_p99_s"] == pytest.approx(p99)
    assert snap["ttft_p99_s"] == pytest.approx(bus.ttft_p99("t"))


def test_measured_t_max_occupancy_floor():
    bus = TelemetryBus(["t"], alpha=1.0)
    bus.record_ready("t", 10)
    # one busy replica out of ten ready: occupancy 0.1 clips to the 0.25
    # floor, so the capacity extrapolation is rate/0.25, not rate/0.1
    bus.record_pump("t", "t/r1", _pump_report(completed=2), queue_depth=0)
    bus.roll(1.0)
    rate = bus.tier_rate["t"].get()
    out = bus.measured_t_max(np.array([7.0]))
    assert out[0] == pytest.approx(rate / 0.25)
    # tiers with no measurements fall back to nominal
    bus2 = TelemetryBus(["t"])
    assert bus2.measured_t_max(np.array([7.0]))[0] == 7.0


def test_telemetry_exposition_has_histogram_families():
    bus = TelemetryBus(["t"])
    bus.record_completion("t", "t/r1", ttft_s=0.2, tpot_s=0.01, tokens=4)
    bus.record_pump("t", "t/r1", _pump_report(), queue_depth=0)
    text = bus.exposition()
    assert '# TYPE fleet_ttft_seconds histogram' in text
    assert 'fleet_ttft_seconds_count{tier="t"} 1' in text
    assert 'fleet_tpot_seconds_count{tier="t"} 1' in text
    assert 'fleet_pump_wall_seconds_count{tier="t"} 1' in text
    assert 'fleet_completions_total{tier="t"} 1' in text


# ---------------------------------------------------------------------------
# Exporters on synthetic traces (no engine)
# ---------------------------------------------------------------------------


def _synthetic_events():
    return [
        {"t": 0.0, "name": "ctl.mode_switch", "cat": "ctl", "mode": 1,
         "prev_mode": 0, "demand": 5.0, "pool": [2]},
        {"t": 0.0, "name": "replica.ready", "cat": "ctl", "replica": "a",
         "tier": "spot"},
        _ev("req.queued", 0.0, rid=1, prompt_len=8),
        _ev("req.dispatched", 1.0, rid=1, replica="a", tier="spot"),
        {"t": 1.0, "name": "engine.pump", "cat": "engine", "replica": "a",
         "tier": "spot", "wall_s": 0.1, "admit_s": 0.02, "dispatch_s": 0.05,
         "sync_s": 0.03, "occupancy": 0.5},
        _ev("req.first_token", 2.0, rid=1, replica="a"),
        _ev("req.requeued", 3.0, rid=1, replica="a", tier="spot"),
        {"t": 3.0, "name": "ctl.replica_fail", "cat": "ctl", "replica": "a",
         "tier": "spot"},
        _ev("req.dispatched", 4.0, rid=1, replica="b", tier="spot"),
        _ev("req.completed", 6.0, rid=1, replica="b", tier="spot", tokens=4),
    ]


def test_trace_export_builds_valid_chrome_trace():
    doc = trace_export.convert(_synthetic_events())
    text = json.dumps(doc)
    parsed = json.loads(text)                  # valid JSON end to end
    evs = parsed["traceEvents"]
    # one serve slice per replica visited, prefill/decode nested in the 1st
    serves = [e for e in evs if e["ph"] == "X" and e["name"] == "serve r1"]
    assert len(serves) == 2
    assert {s["args"]["replica"] for s in serves} == {"a", "b"}
    a_slice = next(s for s in serves if s["args"]["replica"] == "a")
    assert a_slice["ts"] == 1.0 * 1e6 and a_slice["dur"] == 2.0 * 1e6
    names = [e["name"] for e in evs]
    assert "prefill" in names and "decode" in names
    assert "ctl.mode_switch" in names          # control-plane instants
    # replica processes are named
    procs = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert any(p["args"]["name"] == "replica a" for p in procs)
    frac, ok, total = trace_export.coverage(_synthetic_events())
    assert (frac, ok, total) == (1.0, 1, 1)


def test_trace_export_coverage_counts_sliceless_requests():
    # a completed request with no dispatch anywhere has no serve slice
    events = [_ev("req.queued", 0.0, rid=1),
              _ev("req.completed", 1.0, rid=1, replica="a")]
    frac, ok, total = trace_export.coverage(events)
    assert total == 1 and ok == 0 and frac == 0.0


def test_fleet_top_aggregates_and_renders():
    top = fleet_top.FleetTop()
    for ev in _synthetic_events():
        top.feed(ev)
    out = top.render()
    assert "fleet_top @ t=6.0s" in out
    assert "1 completed, 1 requeued" in out
    assert "mode=capacity" in out and "failures=1" in out
    # replica rows: a dispatched 1, b dispatched 1 + completed 1
    a_row = next(l for l in out.splitlines() if l.startswith("a "))
    b_row = next(l for l in out.splitlines() if l.startswith("b "))
    assert a_row.split()[3] == "1" and b_row.split()[4] == "1"


# ---------------------------------------------------------------------------
# The audit drill: kills + preemption over a live fleet (slow lane)
# ---------------------------------------------------------------------------

PLEN = 96
MAX_NEW = (8, 12)
PAGE = 16
MAX_LEN = -(-(PLEN + MAX_NEW[1]) // PAGE) * PAGE          # 112
NUM_PAGES = 1 + 2 * 3 * (MAX_LEN // PAGE)                 # 43


@pytest.fixture(scope="module")
def spot_engine():
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import EngineConfig, ServingEngine

    cfg = get_config("qwen3-0.6b").reduce()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return ServingEngine(model, params, EngineConfig(
        max_len=MAX_LEN, decode_batch=3, temperature=0.0, decode_chunk=4,
        mixed_step=True, prefill_chunk=64, paged_kv=True, page_size=PAGE,
        num_pages=NUM_PAGES, prefix_reuse=True))


def _within(child, parent):
    return (parent["w"] <= child["w"]
            and child["w"] + child["wall_s"] <= parent["w"] + parent["wall_s"])


def test_fleet_spans_cover_ticks_pumps_and_first_tokens(spot_engine):
    """A paged fleet's record: every tick's phases under ``fleet.tick``,
    every pump's phases under ``engine.pump``, the pump's phase walls read
    from those spans (the prefix cache's logits reads as sync), and each
    request stamped queued -> dispatched -> admitted -> first token on the
    wall clock."""
    from repro.fleet.client import FleetClient
    from repro.fleet.runtime import FleetConfig, FleetRuntime, TierSpec
    from repro.serving.api import InferenceRequest

    tier = TierSpec(name="spot", arch="qwen3-0.6b", max_len=MAX_LEN,
                    decode_batch=3, decode_chunk=4, queue_limit=6,
                    base_capacity=1, initial_replicas=1, paged_kv=True,
                    page_size=PAGE, num_pages=NUM_PAGES, prefill_chunk=64)
    rt = FleetRuntime([tier], [], FleetConfig(seed=0, warmup=False))
    rt._engines["spot"] = spot_engine
    client = FleetClient(rt)
    rng = np.random.default_rng(3)
    vocab = spot_engine.model.cfg.vocab_size
    rids = [client.submit(InferenceRequest(
        prompt=rng.integers(0, vocab, n), max_new=6)).rid
        for n in (40, 70, PLEN, 24)]
    client.drain()
    events = rt.tracer.to_list()
    by = lambda name: [e for e in events if e["name"] == name]  # noqa: E731

    ticks = by("fleet.tick")
    assert len(ticks) == rt.ticks and all(e["parent"] is None for e in ticks)
    for name in ("fleet.intake", "fleet.control", "fleet.dispatch",
                 "fleet.autoscale"):
        phases = by(name)
        assert len(phases) == len(ticks)
        assert all(e["parent"] == "fleet.tick" for e in phases)
        assert all(any(_within(p, tk) for tk in ticks) for p in phases)

    pumps = by("engine.pump")
    assert pumps and all(e["parent"] == "fleet.tick" for e in pumps)
    assert {e["parent"] for e in by("fleet.deliver")} == {"fleet.tick"}
    assert by("pump.publish_sync"), "the paged tier published no prompt"
    for pump in pumps:
        assert pump["replica"] == "spot/r1" and pump["tier"] == "spot"
        kids = [e for e in events if e["name"].startswith("pump.")
                and _within(e, pump)]
        wall = {}
        for k in kids:
            wall[k["name"]] = wall.get(k["name"], 0.0) + k["wall_s"]
        assert pump["admit_s"] == pytest.approx(wall["pump.admit"])
        publish = wall.get("pump.publish_sync", 0.0)
        assert pump["sync_s"] == pytest.approx(
            publish + wall.get("pump.emit_sync", 0.0)
            + wall.get("pump.decode_sync", 0.0))
        assert pump["dispatch_s"] == pytest.approx(
            wall.get("pump.prefill", 0.0) - publish
            + wall.get("pump.decode", 0.0))
        phases = pump["admit_s"] + pump["dispatch_s"] + pump["sync_s"]
        assert 0.5 * pump["wall_s"] <= phases <= pump["wall_s"]
        for k in kids:
            want = "pump.prefill" if k["name"] == "pump.publish_sync" \
                else "engine.pump"
            assert k["parent"] == want
    for pre in by("pump.prefill"):
        assert pre["steps"] >= 1

    chains = request_chains(events)
    for rid in rids:
        chain = chains[rid]
        assert validate_chain(chain) == []
        w = {e["name"]: e["w"] for e in reversed(chain)}   # first of each
        assert (w["req.queued"] <= w["req.dispatched"] <= w["req.admitted"]
                <= w["req.first_token"] <= w["req.completed"])
        (adm,) = [e for e in chain if e["name"] == "req.admitted"]
        assert adm["replica"] == "spot/r1"


def test_step_ops_carry_the_model_scopes(spot_engine):
    """The mixed step's ops name the model's scopes in their metadata,
    where a device trace's op records find them."""
    import re

    sess = spot_engine.new_session()
    b = spot_engine.cfg.decode_batch
    q = spot_engine.chunk_quantum(64)
    text = spot_engine._mixed_paged.lower(
        spot_engine.params, sess.cache, jax.numpy.asarray(sess.tables),
        jax.numpy.zeros((b, q), jax.numpy.int32), sess.tok,
        jax.numpy.zeros((b,), jax.numpy.int32),
        jax.numpy.ones((b,), jax.numpy.int32),
        jax.numpy.zeros((b,), bool), 64).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    scopes = {p for path in paths for p in path.split("/")}
    assert {"attn", "kv_write", "mlp", "fuse_weights", "lm_head"} <= scopes
    assert any("attn/kv_write/" in path for path in paths)


@pytest.mark.slow
def test_recovery_drill_flight_recorder_audit(spot_engine, tmp_path):
    from repro.fleet.runtime import build_recovery_fleet

    rt = build_recovery_fleet(prompt_len=PLEN, max_new=MAX_NEW,
                              page_size=PAGE, kv_store=True)
    rt._engines["spot"] = spot_engine          # reuse compiled jits
    report = rt.run()
    n_req = len(report.requests.records)
    assert n_req > 0 and not report.requests.dropped

    # 1. every controller decision is explainable from its recorded signals
    assert report.decisions, "no decisions in the audit log"
    for rec in report.decisions:
        assert rec.explains(), f"unexplainable decision at t={rec.t}: {rec}"
        assert rec.tiers == ("spot",)
        assert len(rec.pool) == len(rec.tentative) == len(rec.measured_t_max)
    # the audit log and the mode trace agree
    assert [(d.t, d.mode) for d in report.decisions] == report.mode_trace

    # 2. the kills actually migrated work, and every chain stays contiguous
    events = rt.tracer.to_list()
    chains = request_chains(events)
    requeued = {e["rid"] for e in events if e["name"] == "req.requeued"}
    assert requeued, "drill produced no requeues — the kills missed"
    for rid, chain in chains.items():
        assert validate_chain(chain) == [], (
            f"rid {rid} chain violations: {validate_chain(chain)}")
    for rid in requeued:                       # migrated to a new replica
        reps = [e["replica"] for e in chains[rid]
                if e["name"] == "req.dispatched"]
        assert len(reps) >= 2

    # 3. control-plane events carry their context
    assert any(e["name"] == "ctl.preempt_notice" for e in events)
    assert any(e["name"] == "ctl.kv_flush" for e in events)
    assert any(e["name"] == "ctl.kv_restore" for e in events)
    for ev in (e for e in events if e["name"] == "ctl.mode_switch"):
        assert "demand" in ev and "pool" in ev and "reason" in ev

    # 4. JSONL -> Chrome trace: valid JSON, >= 99% request coverage
    path = str(tmp_path / "drill.jsonl")
    rt.tracer.dump_jsonl(path)
    loaded = load_jsonl(path)
    assert len(loaded) == len(events)
    doc = trace_export.convert(loaded)
    parsed = json.loads(json.dumps(doc))
    assert parsed["traceEvents"]
    frac, ok, total = trace_export.coverage(loaded)
    assert total == n_req
    assert frac >= 0.99, f"coverage {ok}/{total}"

    # 5. fleet_top digests the same stream
    top = fleet_top.FleetTop()
    for ev in loaded:
        top.feed(ev)
    out = top.render()
    assert f"{n_req} completed" in out


@pytest.mark.slow
def test_trace_disabled_fleet_records_nothing(spot_engine):
    from repro.fleet.runtime import build_recovery_fleet

    rt = build_recovery_fleet(prompt_len=PLEN, max_new=MAX_NEW,
                              page_size=PAGE, kv_store=True)
    rt.cfg.trace = False
    # rebuild the tracer the way __init__ would have with trace=False
    rt.tracer = Tracer.disabled()
    rt.dispatcher.tracer = rt.tracer
    rt.kv_store.tracer = rt.tracer
    rt._engines["spot"] = spot_engine
    report = rt.run()
    assert len(report.requests.records) > 0
    assert len(rt.tracer.events) == 0
    # the decision audit is part of FleetReport, not the tracer: it stays
    assert report.decisions and all(d.explains() for d in report.decisions)
