"""Cluster-layer integration: the full SEIFER lifecycle, in-process.

init -> leader election -> bandwidth probe -> partition+place -> deploy ->
inference -> node failure -> recovery -> inference again -> model-version
update -> redeploy.  The executor is a real jnp MLP so outputs are checked
end-to-end, not just orchestration state."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ArtifactStore, Dispatcher, EdgeCluster, ModelWatcher
from repro.core.graph import chain
from repro.core.placement import CommGraph
from repro.core.simulate import random_cluster
from repro.dataplane import get_codec


def _mlp_setup(n_layers=8, d=16, seed=0):
    ws = np.asarray(
        jax.random.normal(jax.random.PRNGKey(seed), (n_layers, d, d)) * 0.3
    )

    def executor(start, stop, x):
        for i in range(start, stop):  # partition [start, stop) == ws rows
            x = jnp.tanh(x @ ws[i])
        return x

    # layer graph: embed-like first node + n_layers + head handled as chain
    g = chain("mlp", [(d * d * 4, 4 * d * 4)] * n_layers, in_bytes=4 * d * 4)

    def reference(x):
        for i in range(n_layers):
            x = jnp.tanh(x @ ws[i])
        return x

    return g, executor, reference


def _cluster(n_nodes=8, capacity=3 * 16 * 16 * 4, seed=3):
    comm = random_cluster(n_nodes, capacity, seed=seed)
    return EdgeCluster(comm, flops_per_s=1e9)


def test_full_lifecycle_with_failure():
    g, executor, reference = _mlp_setup()
    cluster = _cluster()
    store = ArtifactStore_tmp()
    disp = Dispatcher(cluster, store, seed=0)

    leader = disp.elect_leader()
    assert leader == 0
    probed = disp.probe_bandwidths()
    assert probed.bw.shape == cluster.comm.bw.shape

    plan = disp.configure(g, version=0, capacity=3 * 16 * 16 * 4)
    assert plan.feasible
    assert plan.partition.n_parts >= 2  # model does not fit one node
    pipe = disp.deploy(plan, executor)

    x = jnp.ones((4, 16)) * 0.2
    y0, trace = pipe.run(x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(reference(x)), rtol=1e-6)
    assert trace.bottleneck_s > 0

    # --- kill a node hosting a partition ---
    victim = pipe.pods[1].node_id
    cluster.fail(victim)
    pipe.mark_node_failed(victim)
    assert not pipe.healthy()
    with pytest.raises(RuntimeError):
        pipe.run(x)

    pipe = disp.recover(pipe, g, version=0)
    assert pipe.healthy()
    assert victim not in pipe.path()
    y1, _ = pipe.run(x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-6)
    assert any(p.restarts > 0 for p in pipe.pods)


def test_compression_reduces_bottleneck():
    g, executor, _ = _mlp_setup()
    cluster = _cluster()
    disp = Dispatcher(cluster, ArtifactStore_tmp(), seed=1)
    plan = disp.configure(g, version=0, capacity=3 * 16 * 16 * 4)
    assert set(plan.codecs) == {"identity"}
    plain = disp.deploy(plan, executor)
    inner = ("int8",) * (plan.n_parts - 1)
    comp = disp.deploy(
        dataclasses.replace(plan, codecs=("identity", *inner, "identity")),
        executor)
    x = jnp.ones((4, 16))
    _, t0 = plain.run(x)
    _, t1 = comp.run(x)
    assert t1.bottleneck_s == pytest.approx(
        t0.bottleneck_s * get_codec("int8").wire_ratio())


def test_model_watch_redeploys():
    g, executor, reference = _mlp_setup()
    cluster = _cluster()
    store = ArtifactStore_tmp()
    disp = Dispatcher(cluster, store, seed=2)
    plan = disp.configure(g, version=0, capacity=3 * 16 * 16 * 4)
    pipe = disp.deploy(plan, executor)
    store.publish(0)

    watcher = ModelWatcher(store, disp, graph_for_version=lambda v: g)
    same = watcher.poll(pipe, executor)
    assert same is pipe  # no new version -> untouched

    store.publish(1)  # external repo pushes a new model version
    new_pipe = watcher.poll(pipe, executor)
    assert new_pipe is not pipe
    assert all(not p.alive for p in pipe.pods)  # old pods stopped
    y, _ = new_pipe.run(jnp.ones((2, 16)))
    assert y.shape == (2, 16)


def test_leader_reelection_on_leader_death():
    g, executor, _ = _mlp_setup()
    cluster = _cluster()
    disp = Dispatcher(cluster, ArtifactStore_tmp(), seed=4)
    plan = disp.configure(g, version=0, capacity=3 * 16 * 16 * 4)
    pipe = disp.deploy(plan, executor)
    cluster.fail(0)  # dispatcher node dies
    pipe.mark_node_failed(0)
    disp.recover(pipe, g, version=0)
    assert disp.leader != 0
    assert disp.leader in cluster.healthy_ids()


def ArtifactStore_tmp():
    import tempfile

    return ArtifactStore(tempfile.mkdtemp(prefix="seifer-store-"))
