"""Tests for the unified observability plane (``repro.obs``).

Covers the four components end to end on real deployments:

  * span tracer -- timelines tile each completed request's life exactly,
    sampling is deterministic, disabled tracing leaves no trace surface,
    churn (node kills mid-serve) never produces malformed timelines
    (property-tested over random kill schedules);
  * control-plane journal -- monotone stamps, recovery/reconcile records
    that agree with ``Dispatcher.last_recovery``;
  * metrics registry -- schema-valid snapshots embedded in
    ``Deployment.metrics()`` without disturbing the legacy shape;
  * critical-path analyzer -- fractions sum to one, bottleneck agreement;
  * wall-clock spans (``seifer.*``) in a profiler capture on the CPU --
    one per stage call and coded hop, with their metadata, and none
    without a session.

Determinism is pinned hard: same-seed runs must serialize byte-identically
(timelines, Chrome traces, and journal dumps).
"""

from __future__ import annotations

import json
import math

import jax.numpy as jnp
import pytest

from repro.api import ClusterSpec, DeploymentSpec, TraceConfig, deploy
from repro.cluster import NodeFailed
from repro.cluster.autoscale import ScaleEvent
from repro.core.model_zoo import demo_mlp
from repro.obs import Journal, analyze_spans, percentile
from repro.obs.critical_path import request_attribution
from repro.obs.metrics import MetricsRegistry, validate_snapshot
from repro.dataplane.hops import split_hop
from repro.obs.trace import SpanTracer, split_window

from tests._hypothesis_compat import given, settings, st

D = 32


def _deploy(sample=1.0, seed=0, **kw):
    graph, executor_for_version = demo_mlp(d=D)
    trace = TraceConfig(sample=sample) if sample is not None else None
    return deploy(DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        cluster=ClusterSpec(n_nodes=8,
                            capacity_bytes=graph.total_param_bytes / 2.5,
                            seed=seed + 3),
        seed=seed,
        trace=trace,
        **kw,
    ))


def _serve(d, n, kill_node=None, kill_after=0):
    x = jnp.ones((D,)) * 0.1
    for _ in range(n):
        d.submit(x)
    killed = kill_node is None
    for _ in range(100_000):
        if not killed and len(d.loop.completed) >= kill_after:
            d.inject(NodeFailed(kill_node))
            killed = True
        if not d.loop.backlog and not d.pending:
            break
        d.step()
    assert not d.loop.backlog and not d.pending, "serve loop did not drain"
    return d


def _assert_contiguous(spans):
    """One request's retained spans form a gapless, overlap-free chain."""
    spans = sorted(spans, key=lambda s: s.t0_s)
    for s in spans:
        assert s.t1_s > s.t0_s
    for a, b in zip(spans, spans[1:]):
        assert abs(b.t0_s - a.t1_s) <= 1e-9, (a, b)


# -- span tracer ------------------------------------------------------------

def test_spans_tile_each_completed_request_exactly():
    d = _serve(_deploy(), 12)
    assert d.loop.completed
    for req in d.loop.completed:
        spans = d.tracer.spans_for(req.req_id)
        assert spans, req.req_id
        _assert_contiguous(spans)
        first = min(s.t0_s for s in spans)
        last = max(s.t1_s for s in spans)
        assert abs(first - req.submitted_s) <= 1e-9
        assert abs(last - req.completed_s) <= 1e-9
        covered = sum(s.duration_s for s in spans)
        assert abs(covered - req.latency_s) <= 1e-9


def test_sampling_is_deterministic_and_partial():
    d1 = _serve(_deploy(sample=0.5), 32)
    d2 = _serve(_deploy(sample=0.5), 32)
    traced1 = {s.req_id for s in d1.tracer.spans}
    traced2 = {s.req_id for s in d2.tracer.spans}
    assert traced1 == traced2  # hash-based, not RNG-state-based
    assert 0 < len(traced1) < 32  # partial sampling really is partial
    for req in d1.loop.completed:
        if req.req_id not in traced1:
            assert d1.tracer.spans_for(req.req_id) == []


def test_disabled_tracing_leaves_no_surface():
    d = _serve(_deploy(sample=None), 8)
    assert d.tracer is None
    assert d.trace_timeline() == []
    assert d.chrome_trace() is None
    assert d.attribution() is None
    assert d.metrics()["observability"]["trace"] is None


def _replicated(sample=1.0, seed=0):
    graph, executor_for_version = demo_mlp(d=D)
    return deploy(DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        cluster=ClusterSpec(n_nodes=16,
                            capacity_bytes=graph.total_param_bytes / 2.5,
                            seed=seed + 3),
        seed=seed,
        replicas=2,
        trace=TraceConfig(sample=sample),
    ))


def test_replicated_loop_attributes_spans_to_replicas():
    d = _serve(_replicated(), 16)
    replicas = {s.replica for s in d.tracer.spans}
    assert replicas and replicas <= {0, 1}
    assert len(replicas) == 2  # both replicas carried sampled requests


def test_max_spans_cap_counts_drops():
    d = _serve(_deploy(), 24)
    full = len(d.tracer.spans)
    assert full > 10
    graph, executor_for_version = demo_mlp(d=D)
    capped = deploy(DeploymentSpec(
        model=graph, executor_for_version=executor_for_version,
        cluster=ClusterSpec(n_nodes=8,
                            capacity_bytes=graph.total_param_bytes / 2.5,
                            seed=3),
        trace=TraceConfig(max_spans=10),
    ))
    _serve(capped, 24)
    assert len(capped.tracer.spans) == 10
    assert capped.tracer.dropped == full - 10
    assert capped.tracer.summary()["dropped"] == full - 10


@settings(max_examples=12, deadline=None)
@given(kill_stage=st.integers(min_value=0, max_value=7),
       kill_after=st.integers(min_value=0, max_value=10))
def test_timelines_stay_well_formed_under_random_node_kills(
        kill_stage, kill_after):
    """Property: whatever node dies whenever, every retained span timeline
    is positive-length, contiguous, and ends at the request's completion;
    journal stamps stay monotone."""
    d = _deploy()
    pods = d.control.pipeline.pods
    node = pods[kill_stage % len(pods)].node_id
    _serve(d, 12, kill_node=node, kill_after=kill_after)
    assert len(d.loop.completed) == 12
    by_req = {}
    for s in d.tracer.spans:
        by_req.setdefault(s.req_id, []).append(s)
    completed = {r.req_id: r for r in d.loop.completed}
    for rid, spans in by_req.items():
        _assert_contiguous(spans)
        req = completed[rid]
        assert abs(max(s.t1_s for s in spans) - req.completed_s) <= 1e-9
    stamps = [r.t_s for r in d.journal.records]
    assert stamps == sorted(stamps)
    assert [r.seq for r in d.journal.records] == list(range(len(stamps)))


def test_same_seed_runs_serialize_byte_identically():
    a = _serve(_deploy(), 16)
    b = _serve(_deploy(), 16)
    assert json.dumps(a.trace_timeline()) == json.dumps(b.trace_timeline())
    assert json.dumps(a.chrome_trace()) == json.dumps(b.chrome_trace())
    assert (json.dumps(a.journal.as_dicts())
            == json.dumps(b.journal.as_dicts()))


def test_chrome_trace_is_structurally_valid():
    d = _serve(_deploy(), 12)
    trace = d.chrome_trace()
    json.dumps(trace)  # serializable as-is
    events = trace["traceEvents"]
    assert any(ev["ph"] == "M" and ev["name"] == "process_name"
               for ev in events)
    tracks = {}
    for ev in events:
        assert {"ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "M":
            continue
        assert ev["ph"] == "X" and ev["dur"] >= 0
        tracks.setdefault((ev["pid"], ev["tid"]), []).append(
            (ev["ts"], ev["dur"]))
    for spans in tracks.values():
        spans.sort()
        for (t0, dur), (t1, _) in zip(spans, spans[1:]):
            assert t1 >= t0 + dur - 1e-6  # per-request tracks never overlap


# -- control-plane journal --------------------------------------------------

def test_journal_monotone_with_skewed_clocks_and_stamp_overrides():
    j = Journal()
    j.bind_clock(lambda: 5.0)
    j.bind_clock(lambda: 3.0)
    r1 = j.append("reconcile", "control", {"action": "noop"})
    assert r1.t_s == 5.0  # max across providers
    r2 = j.append("scale", "autoscaler", {}, t_s=1.0)
    assert r2.t_s == 5.0  # explicit stamps are clamped monotone
    r3 = j.append("scale", "autoscaler", {}, t_s=9.0)
    assert r3.t_s == 9.0
    assert [r.seq for r in j.records] == [0, 1, 2]
    assert j.summary()["kinds"] == {"reconcile": 1, "scale": 2}
    assert j.select(kind="scale") == [r2, r3]
    assert j.select(source="control") == [r1]


def test_node_kill_journals_recovery_matching_dispatcher():
    d = _deploy()
    node = d.control.pipeline.pods[1].node_id
    _serve(d, 16, kill_node=node, kill_after=4)
    recoveries = d.journal.select(kind="recovery")
    assert recoveries
    last = d.control.dispatcher.last_recovery
    rec = recoveries[-1].detail
    assert rec["affected_stages"] == list(last["affected_stages"])
    assert rec["scoped"] == last["scoped"]
    assert rec["fallback"] == last["fallback"]
    assert d.journal.select(kind="reconcile")  # the replace was journaled
    # the dispatcher's own log mirrors what the journal saw
    assert d.control.dispatcher.recovery_log
    assert d.control.dispatcher.recovery_log[-1] == last


def test_metrics_surfaces_recovery_log_and_journal():
    d = _deploy()
    node = d.control.pipeline.pods[1].node_id
    _serve(d, 16, kill_node=node, kill_after=4)
    out = d.metrics()
    assert out["recovery"]["last"] == d.control.dispatcher.last_recovery
    assert out["recovery"]["log"] == d.control.dispatcher.recovery_log
    assert out["journal"]["records"] == len(d.journal)
    assert out["journal"]["kinds"].get("recovery", 0) >= 1


# -- metrics registry -------------------------------------------------------

def test_registry_snapshot_validates_and_counts_requests():
    d = _serve(_deploy(), 12)
    out = d.metrics()
    snap = out["observability"]["metrics"]
    validate_snapshot(snap)
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["requests_completed"] == 12
    # legacy metrics keys survive (the registry view is additive)
    assert "serving" in out or "requests" in out or "backlog" in out


def test_registry_rejects_malformed_snapshots():
    from repro.obs.metrics import SnapshotSchemaError

    reg = MetricsRegistry()
    reg.counter("ok").inc()
    snap = reg.snapshot()
    validate_snapshot(snap)
    snap["counters"][0]["value"] = float("nan")
    with pytest.raises(SnapshotSchemaError):
        validate_snapshot(snap)


def test_scale_event_carries_its_measurement():
    ev = ScaleEvent(t_s=1.0, action="grow", replica=2,
                    reason="backlog_per_replica>16", live_after=3,
                    measurement=24.5)
    assert ev.summary()["measurement"] == 24.5
    restore = ScaleEvent(t_s=2.0, action="restore", replica=0,
                         reason="no live replicas", live_after=1)
    assert restore.summary()["measurement"] is None


# -- critical-path analyzer -------------------------------------------------

def test_attribution_fractions_sum_to_one():
    d = _serve(_deploy(), 12)
    att = analyze_spans(d.tracer.spans)
    assert abs(sum(att["fractions"].values()) - 1.0) <= 1e-6
    assert att["requests"] == 12
    assert att["bottleneck"]["kind"] in ("stage", "link")
    for spans_of_req in (d.tracer.spans_for(r.req_id)
                         for r in d.loop.completed[:3]):
        per = request_attribution(spans_of_req)
        groups = ("queue", "compute", "wire", "transcode")
        assert abs(sum(per[g] for g in groups) - 1.0) <= 1e-6
        assert per["total_s"] > 0


def test_split_window_tiles_exactly_and_handles_dead_links():
    segs = split_window(1.0, 2.0, (0.25, 0.5, 0.25))
    assert [p for p, _, _ in segs] == ["encode", "wire", "decode"]
    assert abs(sum(b - a for _, a, b in segs) - 1.0) <= 1e-12
    for (_, _, b), (_, a, _) in zip(segs, segs[1:]):
        assert a == b  # shared boundaries: telescoping by construction
    assert split_window(1.0, 2.0, (0.0, float("inf"), 0.0)) == [
        ("wire", 1.0, 2.0)]
    assert split_window(2.0, 2.0, (0.1, 0.1, 0.1)) == []
    enc, wire, dec = split_hop(float("inf"), None, 1024)
    assert (enc, dec) == (0.0, 0.0) and math.isinf(wire)


# -- spec validation --------------------------------------------------------

def test_trace_config_validation():
    assert TraceConfig().issues() == []
    assert TraceConfig(sample=2.0).issues()
    assert TraceConfig(sample=-0.1).issues()
    assert TraceConfig(max_spans=0).issues()
    graph, executor_for_version = demo_mlp(d=D)
    spec = DeploymentSpec(
        model=graph, executor_for_version=executor_for_version,
        cluster=ClusterSpec(n_nodes=8, capacity_bytes=1e9),
        trace=TraceConfig(sample=7.0))
    assert any("trace" in i.message for i in spec.validate())


# -- shared stats helper ----------------------------------------------------

def test_percentile_has_one_nearest_rank_implementation():
    from repro.cluster.serving import percentile as served
    assert served is percentile
    vals = sorted(float(v) for v in range(1, 101))
    assert percentile(vals, 0.50) == 50.0
    assert percentile(vals, 0.99) == 99.0
    assert percentile(vals, 1.00) == 100.0


# -- wall-clock spans on the profiler's clock ---------------------------------

def _seifer_events(trace_dir) -> list[dict]:
    """Every ``seifer.*`` host event of a profiler capture, in time order."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("seifer."):
                    out.append({"name": e.name, "t0": e.start_ns, "t1": e.end_ns,
                                "thread": line.name, "args": dict(e.stats)})
    return sorted(out, key=lambda e: (e["t0"], -e["t1"]))


BATCHES = 3  # of the default microbatch, 4


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Three batches of four requests served with int8 hops under a
    profiler session on the CPU."""
    import jax

    d = _deploy(sample=None, codec="int8")
    trace_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(trace_dir)):
        _serve(d, 4 * BATCHES)
    return d, _seifer_events(trace_dir)


def _inside(inner, outer) -> bool:
    return (outer["thread"] == inner["thread"]
            and outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"])


def test_profiler_spans_time_each_stage_call_inside_its_step(profiled):
    d, events = profiled
    steps = [e for e in events if e["name"] == "seifer.step"]
    stage_spans = [e for e in events if e["name"] == "seifer.stage"]
    parts = [p.partition for p in d.control.pipeline.pods]
    assert len(stage_spans) == len(parts) * BATCHES
    for e in stage_spans:
        assert any(_inside(e, s) for s in steps)
    for s, part in enumerate(parts):
        mine = [e["args"] for e in stage_spans if e["args"]["stage"] == s]
        assert mine == [{"stage": s, "first": part.start, "stop": part.stop,
                         "batch": 4}] * BATCHES


def test_profiler_spans_time_each_coded_hop(profiled):
    d, events = profiled
    coded = [h for h, link in enumerate(d.loop._hops) if link.codec is not None]
    assert coded
    spans = [e["args"] for e in events if e["name"] == "seifer.codec"]
    assert sorted(a["hop"] for a in spans) == sorted(coded * BATCHES)
    assert all(a["codec"] == d.loop._hops[a["hop"]].codec.name for a in spans)
    assert "int8" in {a["codec"] for a in spans}
    assert {a["op"] for a in spans} <= {"transcode", "encode"}


def test_profiler_spans_name_admission_and_completion(profiled):
    _, events = profiled
    steps = [e for e in events if e["name"] == "seifer.step"]
    for name in ("seifer.admit", "seifer.complete"):
        spans = [e for e in events if e["name"] == name]
        assert len(spans) == BATCHES, name
        assert all(e["args"] == {"batch": 4} for e in spans)
        assert all(any(_inside(e, s) for s in steps) for e in spans)


def test_no_profiler_span_is_recorded_without_a_session(tmp_path):
    import gc

    import jax

    d = _serve(_deploy(sample=None, codec="int8"), 8)  # no session open
    gc.collect()
    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    assert d.loop.completed
    assert _seifer_events(tmp_path) == []


def test_gc_span_records_each_collection_inside_a_session(tmp_path):
    import gc

    import jax

    from repro.obs import install_gc_span
    from repro.obs.profiler import _on_gc

    install_gc_span()
    install_gc_span()
    assert gc.callbacks.count(_on_gc) == 1
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    spans = [e for e in _seifer_events(tmp_path) if e["name"] == "seifer.gc"]
    assert spans and spans[-1]["args"] == {"generation": 2}
    assert all(e["t1"] >= e["t0"] for e in spans)
