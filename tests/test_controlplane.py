"""Reconciler coverage: the Sec. 2.3 convergence rules, event by event.

  * version bump  -> in-place redeploy, NO full cluster restart
  * node failure  -> re-place onto healthy nodes only
  * node join     -> full restart (generation bump, re-probe, re-partition)
  * link degraded -> re-place only when the bottleneck actually worsens
  * serving loop  -> in-flight requests complete or are retried, never lost
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (
    ArtifactStore,
    ControlPlane,
    EdgeCluster,
    LinkDegraded,
    ModelWatcher,
    NodeFailed,
    NodeJoined,
    PipelinedServingLoop,
    VersionBumped,
)
from repro.core.graph import chain
from repro.core.simulate import expand_cluster, random_cluster
from repro.runtime.pipeline import make_layer_executor

D, LAYERS = 16, 8
CAPACITY = 3 * D * D * 4


def _weights(version, n_layers=LAYERS, d=D):
    return np.asarray(
        jax.random.normal(jax.random.PRNGKey(version), (n_layers, d, d)) * 0.3
    )


def _executor_for_version(version):
    ws = _weights(version)
    return make_layer_executor(
        [lambda x, w=ws[i]: jnp.tanh(x @ w) for i in range(LAYERS)]
    )


def _reference(version, x):
    for w in _weights(version):
        x = jnp.tanh(x @ w)
    return x


def _control(seed=0, n_nodes=8, with_positions=False):
    graph = chain("mlp", [(D * D * 4, 4 * D * 4)] * LAYERS, in_bytes=4 * D * 4)
    comm, pos = random_cluster(n_nodes, CAPACITY, seed=3, with_positions=True)
    cluster = EdgeCluster(comm, flops_per_s=1e9)
    store = ArtifactStore(tempfile.mkdtemp(prefix="seifer-cp-"))
    control = ControlPlane(
        cluster, store, lambda v: graph, _executor_for_version,
        capacity=CAPACITY, seed=seed,
    )
    control.bootstrap(0)  # constructor capacity/compression are the defaults
    return (control, pos) if with_positions else control


def test_version_bump_redeploys_in_place():
    control = _control()
    old_pods = list(control.pipeline.pods)
    gen0 = control.generation
    leader0 = control.dispatcher.leader
    probed0 = control.dispatcher.probed

    control.store.publish(1)
    watcher = ModelWatcher(control.store)
    assert watcher.poll_events(control)
    (action,) = control.reconcile()

    assert action.kind == "redeploy"
    obs = control.observed()
    assert obs.version == 1
    # in-place: no full cluster restart -- same generation, same leader,
    # and the probed bandwidths were NOT re-measured
    assert control.generation == gen0
    assert control.dispatcher.leader == leader0
    assert control.dispatcher.probed is probed0
    assert all(not p.alive for p in old_pods)  # old pods stopped
    # new pipeline really computes the NEW version's weights
    x = jnp.ones((2, D)) * 0.1
    y, _ = control.pipeline.run(x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_reference(1, x)), rtol=1e-6
    )


def test_infeasible_version_bump_keeps_old_deployment():
    """An infeasible new version must not take down the healthy pipeline."""
    graph_v0 = chain("mlp", [(D * D * 4, 4 * D * 4)] * LAYERS, in_bytes=4 * D * 4)
    too_big = chain("huge", [(100 * CAPACITY, 4)] * LAYERS)
    comm = random_cluster(8, CAPACITY, seed=3)
    store = ArtifactStore(tempfile.mkdtemp(prefix="seifer-cp-"))
    control = ControlPlane(
        EdgeCluster(comm, flops_per_s=1e9), store,
        lambda v: too_big if v > 0 else graph_v0, _executor_for_version,
        capacity=CAPACITY,
    )
    control.bootstrap(0)
    control.submit(VersionBumped(1))
    (action,) = control.reconcile()
    assert action.kind == "noop" and "rejected" in action.detail
    obs = control.observed()
    assert obs.version == 0 and obs.healthy  # v0 still serving
    y, _ = control.pipeline.run(jnp.ones((2, D)))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_reference(0, jnp.ones((2, D)))), rtol=1e-6
    )


def test_stale_version_bump_is_noop():
    control = _control()
    control.submit(VersionBumped(0))
    (action,) = control.reconcile()
    assert action.kind == "noop"
    assert control.observed().version == 0


def test_node_failure_replaces_onto_healthy_nodes():
    control = _control()
    x = jnp.ones((2, D)) * 0.2
    y0, _ = control.pipeline.run(x)
    victim = control.pipeline.pods[1].node_id

    control.submit(NodeFailed(victim))
    (action,) = control.reconcile()

    assert action.kind == "replace"
    obs = control.observed()
    assert obs.healthy
    assert victim not in obs.path
    assert set(obs.path) <= set(control.cluster.healthy_ids())
    assert control.generation == 0  # failure never forces a full restart
    y1, _ = control.pipeline.run(x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-6)


def test_node_failure_on_idle_node_is_noop():
    control = _control()
    idle = next(
        i for i in control.cluster.healthy_ids()
        if i not in control.pipeline.path() and i != control.dispatcher.leader
    )
    path0 = control.pipeline.path()
    control.submit(NodeFailed(idle))
    (action,) = control.reconcile()
    assert action.kind == "noop"
    assert control.pipeline.path() == path0


def test_node_join_triggers_full_restart():
    control, pos = _control(with_positions=True)
    gen0 = control.generation
    n0 = control.cluster.n
    probed0 = control.dispatcher.probed

    comm2, _ = expand_cluster(pos, CAPACITY, seed=11)
    control.submit(NodeJoined(comm=comm2))
    (action,) = control.reconcile()

    assert action.kind == "restart"
    assert control.generation == gen0 + 1
    assert control.cluster.n == n0 + 1
    assert control.dispatcher.probed is not probed0  # re-probed from scratch
    obs = control.observed()
    assert obs.healthy
    y, _ = control.pipeline.run(jnp.ones((2, D)))
    assert y.shape == (2, D)


def test_legacy_poll_without_dispatcher_raises_clearly():
    control = _control()
    watcher = ModelWatcher(control.store)  # control-plane-style construction
    control.store.publish(99)
    with pytest.raises(RuntimeError, match="poll_events"):
        watcher.poll(control.pipeline, _executor_for_version(0))


def test_infeasible_node_join_keeps_old_deployment():
    """A join whose post-restart configure fails must not kill serving."""
    control, pos = _control(with_positions=True)
    y0, _ = control.pipeline.run(jnp.ones((2, D)) * 0.2)
    # make the desired graph impossible to place from now on
    control.desired = __import__("dataclasses").replace(
        control.desired,
        graph=chain("huge", [(100 * CAPACITY, 4)] * LAYERS),
    )
    comm2, _ = expand_cluster(pos, CAPACITY, seed=11)
    control.submit(NodeJoined(comm=comm2))
    (action,) = control.reconcile()
    assert action.kind == "noop" and "rejected" in action.detail
    assert control.generation == 0  # no restart happened
    assert control.observed().healthy  # old pipeline still serving
    y1, _ = control.pipeline.run(jnp.ones((2, D)) * 0.2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-6)


def test_failed_node_rejoin_triggers_full_restart():
    control = _control()
    victim = control.pipeline.pods[1].node_id
    control.submit(NodeFailed(victim))
    control.reconcile()
    control.submit(NodeJoined(node_id=victim))
    (action,) = control.reconcile()
    assert action.kind == "restart"
    assert control.generation == 1
    assert control.cluster.nodes[victim].healthy


def test_link_degraded_within_tolerance_is_noop():
    control = _control()
    # a link between two nodes NOT adjacent on the path: harmless
    path = control.pipeline.path()
    others = [i for i in range(control.cluster.n) if i not in path]
    control.submit(LinkDegraded(others[0], others[1], 0.01))
    (action,) = control.reconcile()
    assert action.kind == "noop"


def test_link_degraded_on_path_replaces():
    control = _control()
    a, b = control.pipeline.path()[:2]
    before = control.observed().bottleneck_latency
    control.submit(LinkDegraded(a, b, 1e-4))
    (action,) = control.reconcile()
    assert action.kind == "replace"
    assert control.observed().bottleneck_latency < before * 1e3  # not stuck on dead link
    assert control.observed().healthy


def test_event_validation():
    with pytest.raises(ValueError):
        NodeJoined()  # neither node_id nor comm
    with pytest.raises(ValueError):
        LinkDegraded(0, 1, -0.5)


# ---------------------------------------------------------------------------
# Serving loop across recovery
# ---------------------------------------------------------------------------

def test_inflight_requests_survive_node_kill():
    control = _control()
    loop = PipelinedServingLoop(control, microbatch=4)
    n = 20
    for _ in range(n):
        loop.submit(jnp.ones((D,)) * 0.1)
    killed = False
    while loop.backlog or control.pending:
        if not killed and len(loop.completed) >= n // 2:
            control.submit(NodeFailed(control.pipeline.pods[1].node_id))
            killed = True
        loop.step()
    assert killed
    assert len(loop.completed) == n
    assert len(loop.failed) == 0
    expected = _reference(0, jnp.ones((D,)) * 0.1)
    for req in loop.completed:
        np.testing.assert_allclose(
            np.asarray(req.result), np.asarray(expected), rtol=1e-5
        )


def test_inflight_requests_retried_on_unannounced_failure():
    """Infra-level failure (no event): pipeline raises mid-batch, the loop
    re-queues, and the drift check repairs the pipeline."""
    control = _control()
    loop = PipelinedServingLoop(control, microbatch=4)
    for _ in range(16):
        loop.submit(jnp.ones((D,)) * 0.1)
    loop.step()
    # the node dies WITHOUT an event: only the cluster + pods know; the
    # batches riding into and out of its stage are in flight
    assert {("link", 1), ("link", 2)} <= {mb.location for mb in loop._inflight}
    victim = control.pipeline.pods[1].node_id
    control.cluster.fail(victim)
    control.pipeline.mark_node_failed(victim)
    loop.drain()
    assert len(loop.completed) == 16
    assert len(loop.failed) == 0
    assert any(r.attempts > 0 for r in loop.completed)
    assert any(
        a.kind == "replace" and a.event is None for a in control.history
    )  # drift-check repair, not event-driven


def test_serving_across_version_bump_switches_weights():
    control = _control()
    loop = PipelinedServingLoop(control, microbatch=4)
    for _ in range(4):
        loop.submit(jnp.ones((D,)) * 0.1)
    loop.drain()
    control.store.publish(1)
    ModelWatcher(control.store).poll_events(control)
    for _ in range(4):
        loop.submit(jnp.ones((D,)) * 0.1)
    loop.drain()
    assert len(loop.completed) == 8
    ref0 = _reference(0, jnp.ones((D,)) * 0.1)
    ref1 = _reference(1, jnp.ones((D,)) * 0.1)
    # atol: a stacked microbatch rounds differently from one request, by
    # float32 rounding at these magnitudes (outputs near 0.01)
    np.testing.assert_allclose(
        np.asarray(loop.completed[3].result), np.asarray(ref0), rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(loop.completed[-1].result), np.asarray(ref1), rtol=1e-5,
        atol=1e-6,
    )
