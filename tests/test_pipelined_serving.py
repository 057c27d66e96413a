"""Discrete-event pipelined serving engine: virtual-clock invariants.

  * steady-state throughput pins to the Planner's bottleneck prediction
    (within 5%), for both link-bound and compute-bound pipelines;
  * no request is lost or duplicated under arbitrary event sequences
    (node kills, version bumps, link degradations, unannounced failures);
  * backpressure bounds every stage queue at ``queue_depth``;
  * in-flight requeue hits exactly the batches resident on affected stages;
  * the pipelined engine beats one-microbatch-at-a-time execution (the
    plan's end-to-end latency per microbatch) by >= 2x at >= 8 partitions
    (the paper's 200% claim, pinned as a test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.cluster import LinkDegraded, NodeFailed
from repro.cluster.engine import PipelinedServingLoop
from repro.core.bottleneck import evaluate_pipeline
from repro.core.graph import Layer, LayerGraph
from repro.core.model_zoo import demo_mlp


def _synth_graph(n_layers=16, param=1_000_000, act=200_000, flops=50_000_000):
    layers = tuple(
        Layer(f"l{i}", param_bytes=param, out_bytes=act, flops=flops)
        for i in range(n_layers)
    )
    return LayerGraph(f"synth{n_layers}", layers, in_bytes=act // 2)


def _deploy(graph, *, n_nodes=10, parts_cap_frac=None, seed=0,
            microbatch=1, queue_depth=2, **kw):
    capacity = (
        graph.total_param_bytes * parts_cap_frac
        if parts_cap_frac is not None
        else graph.total_param_bytes / 6
    )
    spec = DeploymentSpec(
        model=graph,
        cluster=ClusterSpec(n_nodes=n_nodes, capacity_bytes=capacity, seed=seed + 3),
        capacity=capacity,
        seed=seed,
        microbatch=microbatch,
        queue_depth=queue_depth,
        **kw,
    )
    return deploy(spec)


# ---------------------------------------------------------------------------
# Throughput pins to the Planner's prediction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_steady_state_throughput_matches_planner_prediction(seed):
    """Measured steady-state rate == 1/bottleneck predicted by the Planner
    (same service_times model, same probed bandwidths) within 5%."""
    d = _deploy(_synth_graph(), seed=seed)
    for _ in range(150):
        d.submit(jnp.ones((4,)))
    d.drain()
    assert not d.loop.failed
    measured = d.loop.steady_state_throughput()
    predicted = d.plan.predicted_throughput  # microbatch==1: same units
    assert measured == pytest.approx(predicted, rel=0.05)


def test_link_bound_pipeline_also_pins_to_prediction():
    """flops=0 makes every stage free: the bottleneck is a link."""
    d = _deploy(_synth_graph(flops=0), seed=1)
    for _ in range(150):
        d.submit(jnp.ones((4,)))
    d.drain()
    measured = d.loop.steady_state_throughput()
    assert measured == pytest.approx(d.plan.predicted_throughput, rel=0.05)
    # sanity: the prediction really is the bottleneck-hop rate
    m = d.loop.metrics()
    bottleneck = max(max(m["link_s"]), max(s["compute_s"] for s in m["stages"]))
    assert measured == pytest.approx(1.0 / bottleneck, rel=0.05)


# ---------------------------------------------------------------------------
# Conservation: no request lost or duplicated
# ---------------------------------------------------------------------------

def _conservation(loop, submitted):
    done_ids = [r.req_id for r in loop.completed]
    failed_ids = [r.req_id for r in loop.failed]
    queued_ids = [r.req_id for r in loop.queue]
    inflight_ids = [r.req_id for mb in loop._inflight for r in mb.requests]
    everything = done_ids + failed_ids + queued_ids + inflight_ids
    assert len(everything) == len(set(everything)), "request duplicated"
    assert sorted(everything) == sorted(submitted), "request lost"


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_no_request_lost_or_duplicated_under_random_events(seed):
    """Arbitrary interleavings of kills/degradations/version bumps while
    the pipe is full: every admitted request stays accounted for, and all
    of them eventually complete."""
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3, seed=seed,
                microbatch=2, executor_for_version=executor_for_version)
    rng = np.random.default_rng(seed)
    n = 60
    ids = [d.submit(jnp.ones((16,)) * 0.1).req_id for _ in range(n)]
    events = 0
    while d.loop.backlog or d.control.pending:
        if rng.random() < 0.15 and events < 8:
            events += 1
            roll = rng.random()
            pods = d.control.pipeline.pods
            if roll < 0.4:
                d.inject(NodeFailed(pods[rng.integers(len(pods))].node_id))
            elif roll < 0.6:
                victim = pods[rng.integers(len(pods))].node_id
                d.control.cluster.fail(victim)  # unannounced: no event
                d.control.pipeline.mark_node_failed(victim)
            elif roll < 0.8:
                a, b = rng.choice(d.cluster.n, size=2, replace=False)
                d.inject(LinkDegraded(int(a), int(b), 0.5))
            else:
                d.store.publish(d.observed().version + 1)
                d.poll_model_updates()
        d.step()
        _conservation(d.loop, ids)
    assert events > 0
    assert len(d.loop.completed) == n
    assert not d.loop.failed
    # completions carry the CURRENT version's math at completion time: check
    # the last request against the final deployed version's reference
    version = d.observed().version
    x = jnp.ones((16,)) * 0.1
    ws = np.asarray(jax.random.normal(jax.random.PRNGKey(version), (8, 16, 16)) * 0.3)
    for w in ws:
        x = jnp.tanh(x @ w)
    # atol: a stacked microbatch rounds differently from one request, by
    # float32 rounding at these magnitudes (outputs near 0.01)
    np.testing.assert_allclose(
        np.asarray(d.loop.completed[-1].result), np.asarray(x), rtol=1e-5,
        atol=1e-6,
    )


@pytest.mark.parametrize("replicas", [1, 2],
                         ids=["pipelined-1", "pipelined-2"])
def test_device_fault_in_a_stage_propagates(replicas):
    """A ``JaxRuntimeError`` raised by a stage executor (a kernel or memory
    fault on the device) is not a dead pod: it leaves ``step()``/``drain()``
    as is, with no requeue, no re-placement, no retired replica and no
    failed request."""
    graph, executor_for_version = demo_mlp(d=16)

    def faulty_for_version(version):
        ex = executor_for_version(version)
        calls = []

        def run(start, stop, x):
            calls.append(start)
            if len(calls) == 3:
                raise jax.errors.JaxRuntimeError("INTERNAL: injected device fault")
            return ex(start, stop, x)

        return run

    d = _deploy(graph, n_nodes=12, parts_cap_frac=1 / 3, microbatch=2,
                replicas=replicas,
                executor_for_version=faulty_for_version)
    for _ in range(8):
        d.submit(jnp.ones((16,)) * 0.1)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        d.drain()
    assert not d.loop.failed
    replicas_m = d.metrics().get("replicas", [d.metrics()])
    assert all(
        a not in ("replace", "retire")
        for r in replicas_m for a in r["reconcile_actions"]
    )


# ---------------------------------------------------------------------------
# Backpressure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("queue_depth", [1, 2, 4])
def test_backpressure_bounds_every_queue(queue_depth):
    """With a slow bottleneck stage and a deep backlog, no stage's in-queue
    (incl. reserved in-transit slots) ever exceeds queue_depth."""
    # last stage is the bottleneck: cheap links, one expensive compute
    layers = [Layer(f"l{i}", 1_000_000, 10_000, flops=1_000_000) for i in range(11)]
    layers.append(Layer("heavy", 1_000_000, 10_000, flops=500_000_000))
    graph = LayerGraph("skewed", tuple(layers), in_bytes=10_000)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 4, seed=2,
                queue_depth=queue_depth)
    for _ in range(80):
        d.submit(jnp.ones((4,)))
    while d.loop.backlog:
        d.step()
        for st in d.loop._stages:
            assert len(st.queue) + st.reserved <= queue_depth
    m = d.loop.metrics()
    assert all(s["max_queue"] <= queue_depth for s in m["stages"])
    # the bottleneck stage saturates; everyone upstream is throttled to it
    occ = [s["occupancy"] for s in m["stages"]]
    assert max(occ) > 0.9


# ---------------------------------------------------------------------------
# Requeue granularity: exactly the affected stages
# ---------------------------------------------------------------------------

def test_requeue_hits_only_batches_on_affected_stages():
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3, seed=0,
                microbatch=1, executor_for_version=executor_for_version)
    loop = d.loop
    n = 30
    for _ in range(n):
        d.submit(jnp.ones((16,)) * 0.1)
    # fill the pipe, then kill the node hosting stage 1 mid-flight
    while len(loop.completed) < n // 3:
        d.step()
    pods = d.control.pipeline.pods
    victim_stage = 1
    victim = pods[victim_stage].node_id
    k = len(pods)
    resident = set()
    for mb in loop._inflight:
        kind, idx = mb.location
        if kind == "link":
            # hop 0 is a free retransmission (dispatcher still holds the
            # input), so only hops adjacent to the victim stage count
            touches = idx > 0 and (
                (idx - 1) == victim_stage or (idx < k and idx == victim_stage)
            )
        else:
            touches = idx == victim_stage
        if touches:
            resident.update(r.req_id for r in mb.requests)
    spared = {
        r.req_id for mb in loop._inflight for r in mb.requests
        if r.req_id not in resident
    }
    d.inject(NodeFailed(victim))
    d.step()
    everywhere = (
        list(loop.queue) + loop.completed
        + [r for mb in loop._inflight for r in mb.requests]
    )
    retried = {r.req_id for r in everywhere if r.attempts > 0}
    assert retried == resident  # exactly the affected batches, no others
    assert all(r.attempts == 0 for r in everywhere if r.req_id in spared)
    d.drain()
    assert len(loop.completed) == n and not loop.failed


def test_version_bump_requeues_everything_in_flight():
    """A version bump replaces weights everywhere: every stage is affected,
    so every in-flight batch restarts and is recomputed with v1 math."""
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3, seed=0,
                microbatch=1, executor_for_version=executor_for_version)
    n = 24
    for _ in range(n):
        d.submit(jnp.ones((16,)) * 0.1)
    while len(d.loop.completed) < n // 2:
        d.step()
    # batches on the input hop are free retransmissions, not retries
    inflight = [
        r.req_id for mb in d.loop._inflight for r in mb.requests
        if mb.location != ("link", 0)
    ]
    assert inflight  # the pipe is genuinely full mid-bump
    d.store.publish(1)
    d.poll_model_updates()
    d.drain()
    assert len(d.loop.completed) == n and not d.loop.failed
    by_id = {r.req_id: r for r in d.loop.completed}
    assert all(by_id[i].attempts >= 1 for i in inflight)
    # everything completed after the bump used the v1 weights
    x = jnp.ones((16,)) * 0.1
    ws = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16)) * 0.3)
    for w in ws:
        x = jnp.tanh(x @ w)
    for i in inflight:
        np.testing.assert_allclose(
            np.asarray(by_id[i].result), np.asarray(x), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# The paper's claim: pipelining vs synchronous execution
# ---------------------------------------------------------------------------

def test_pipelined_at_least_2x_sync_at_8_partitions():
    """Synchronous execution pushes one microbatch through every stage and
    link before admitting the next: ``microbatch / end_to_end_latency`` of
    the deployed plan, on the same hop records the engine runs on."""
    graph = _synth_graph(n_layers=16, act=1_000_000, flops=2_000_000)
    d = _deploy(graph, n_nodes=10, parts_cap_frac=2.1 / 16, seed=0)
    assert d.plan.n_parts >= 8
    for _ in range(96):
        d.submit(jnp.ones((4,)))
    d.drain()
    assert not d.loop.failed
    control = d.control
    e2e = evaluate_pipeline(
        [p.partition for p in control.pipeline.pods], control.pipeline.path(),
        control.dispatcher.probed,
        device_flops=control.dispatcher.node_flops(),
        in_bytes=graph.in_bytes, out_bytes=graph.layers[-1].out_bytes,
        dispatcher=control.dispatcher.leader, codecs=d.plan.codecs,
    ).end_to_end_latency
    sync_rate = d.loop.microbatch / e2e
    assert d.loop.steady_state_throughput() >= 2.0 * sync_rate


def test_seeded_int8_timeline_after_a_node_kill_is_pinned():
    """The virtual clock orders the stage calls the chip runs: a seeded
    int8 deployment's timeline across a node kill is pinned to the float
    (literals captured before the hop model moved into one function)."""
    graph, executor_for_version = demo_mlp(d=16)
    capacity = graph.total_param_bytes / 3
    d = deploy(DeploymentSpec(
        model=graph, executor_for_version=executor_for_version,
        cluster=ClusterSpec(n_nodes=8, capacity_bytes=capacity, seed=5),
        capacity=capacity, codec="int8", seed=2, microbatch=2,
        queue_depth=2,
    ))
    assert d.plan.codecs == ("identity", "int8", "int8", "int8", "identity")
    assert list(d.observed().path) == [3, 1, 8, 2]
    for _ in range(12):
        d.submit(jnp.ones((16,)) * 0.1)
    while len(d.loop.completed) < 4:
        d.step()
    d.inject(NodeFailed(d.control.pipeline.pods[1].node_id))
    d.drain()
    assert not d.loop.failed
    assert [p.restarts for p in d.control.pipeline.pods] == [0, 1, 0, 0]
    assert d.loop.clock_s == 0.250814593175975
    done = [r.completed_s
            for r in sorted(d.loop.completed, key=lambda r: r.req_id)]
    assert done == [
        0.0002525222933706767, 0.0002525222933706767,
        0.0003618708016927928, 0.0003618708016927928,
        0.25048645476858855, 0.25048645476858855,
        0.25059583423771736, 0.25059583423771736,
        0.2507052137068462, 0.2507052137068462,
        0.250814593175975, 0.250814593175975,
    ]
    int8 = {"codec": "int8", "raw_bytes": 1024.0, "wire_bytes": 260.0,
            "compression_x": 3.9384615384615387, "transfers": 6}
    raw = {"codec": "identity", "raw_bytes": 1024.0, "wire_bytes": 1024.0,
           "compression_x": 1.0, "transfers": 6}
    assert d.loop.metrics()["links"] == [
        {"hop": 0, **raw, "link_s": 7.497349529462284e-05,
         "utilization": 0.0022046803730524896},
        {"hop": 1, **int8, "link_s": 1.4466557354142307e-05,
         "utilization": 0.0003465424038992645},
        {"hop": 2, **int8, "link_s": 1.6759765986125016e-05,
         "utilization": 0.0003530287721061108},
        {"hop": 3, **int8, "link_s": 1.5204497766924147e-05,
         "utilization": 0.0003655260236333665},
        {"hop": 4, **raw, "link_s": 0.00010937946912882153,
         "utilization": 0.002545632248737956},
    ]


def test_out_of_band_reconcile_requeues_restarted_stages():
    """Calling Deployment.reconcile() directly (not via step) must still
    requeue the batches resident on pods that were restarted, at the next
    step -- the engine detects the pod-signature change."""
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3, seed=0,
                microbatch=1, executor_for_version=executor_for_version)
    n = 24
    ids = [d.submit(jnp.ones((16,)) * 0.1).req_id for _ in range(n)]
    while len(d.loop.completed) < n // 3:
        d.step()
    victim = d.control.pipeline.pods[1].node_id
    d.inject(NodeFailed(victim))
    d.reconcile()  # out of band: the serving loop is not in this call path
    assert any(p.restarts > 0 for p in d.control.pipeline.pods)
    d.drain()
    assert len(d.loop.completed) == n and not d.loop.failed
    assert sorted(r.req_id for r in d.loop.completed) == sorted(ids)
    assert d.loop._requeues >= 1  # the restarted stage's batch went back


def test_dead_link_bounds_retries_instead_of_hanging():
    """A transfer stuck on a zero-bandwidth hop can never finish; the engine
    must retry its riders (attempts -> failed) rather than stall a
    ``while backlog: step()`` loop forever."""
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3, seed=0,
                microbatch=1, executor_for_version=executor_for_version)
    loop = d.loop
    n = 12
    for _ in range(n):
        d.submit(jnp.ones((16,)) * 0.1)
    d.step()
    # the wire between stages 1 and 2 goes dark without any event or any
    # node becoming unhealthy -- the worst case for liveness
    loop._link_s[2] = float("inf")
    steps = 0
    while loop.backlog:
        steps += 1
        assert steps < 5_000, "engine hung on a dead link"
        d.step()
    assert len(loop.completed) + len(loop.failed) == n
    assert loop.failed  # the stalled riders were failed, not leaked
    assert all(r.attempts >= loop.max_attempts for r in loop.failed)


def test_engine_is_the_default_serving_mode():
    graph, executor_for_version = demo_mlp(d=16)
    d = _deploy(graph, n_nodes=8, parts_cap_frac=1 / 3,
                executor_for_version=executor_for_version)
    assert isinstance(d.loop, PipelinedServingLoop)
    assert d.metrics()["serving"]["mode"] == "pipelined"
