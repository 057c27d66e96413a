"""Declarative API coverage: registry, spec validation, planner parity, deploy.

The parity test is the PR's regression anchor: a default-strategy ``Planner``
driven through ``Dispatcher.configure`` must reproduce the pre-refactor
hardcoded pipeline (``partition_min_bottleneck`` + ``place_color_coding`` on
the dispatcher's RNG stream) *exactly* -- same cuts, same node path, same
bottleneck latency -- on several seeded clusters.
"""

import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    ClusterSpec,
    DeploymentSpec,
    InfeasibleSpecError,
    Planner,
    UnknownStrategyError,
    default_strategy,
    deploy,
    get_strategy,
    list_strategies,
    register_strategy,
    strategy_table,
)
from repro.cluster import ArtifactStore, Dispatcher, EdgeCluster, NodeFailed
from repro.core.graph import chain
from repro.core.partitioner import partition_min_bottleneck
from repro.core.placement import CommGraph, place_color_coding
from repro.core.simulate import random_cluster

D, LAYERS = 16, 8
CAPACITY = 3 * D * D * 4


def _graph():
    return chain("mlp", [(D * D * 4, 4 * D * 4)] * LAYERS, in_bytes=4 * D * 4)


def _demo_spec(seed=3, **kw):
    from repro.core.model_zoo import demo_mlp

    graph, _ = demo_mlp(d=32)
    kw.setdefault("model", "demo_mlp")
    kw.setdefault("cluster", ClusterSpec(
        n_nodes=8, capacity_bytes=graph.total_param_bytes / 3, seed=seed))
    return DeploymentSpec(**kw)


# ---------------------------------------------------------------------------
# Registry round-trip
# ---------------------------------------------------------------------------

def test_registry_contains_every_algorithm():
    assert set(list_strategies("partitioner")) == {
        "min_bottleneck", "paper_greedy", "min_sum", "exact_k", "uniform",
        "exhaustive",
    }
    assert set(list_strategies("placer")) == {
        "color_coding", "greedy", "random", "optimal", "hierarchical",
    }
    assert set(list_strategies("joint")) == {"sequential", "joint"}
    # defaults are the paper pipeline, listed first
    assert default_strategy("partitioner") == "min_bottleneck"
    assert default_strategy("placer") == "color_coding"
    assert default_strategy("joint") == "sequential"
    assert list_strategies("partitioner")[0] == "min_bottleneck"


def test_registry_resolves_the_actual_functions():
    assert get_strategy("partitioner", "min_bottleneck").fn is partition_min_bottleneck
    assert get_strategy("placer", "color_coding").fn is place_color_coding
    for kind in ("partitioner", "placer", "joint"):
        for name in list_strategies(kind):
            s = get_strategy(kind, name)
            assert s.name == name and s.kind == kind and callable(s.fn)


def test_unknown_strategy_raises_with_suggestions():
    with pytest.raises(UnknownStrategyError) as ei:
        get_strategy("placer", "color_codng")
    assert "color_coding" in str(ei.value)  # did-you-mean
    assert "greedy" in str(ei.value)  # registered names listed
    with pytest.raises(ValueError, match="kind"):
        get_strategy("scheduler", "foo")


def test_unknown_codec_raises_with_suggestions():
    """The codec registry mirrors the strategy registry's ergonomics: a typo
    fails with did-you-mean suggestions, and a spec naming it surfaces the
    same message as a structured issue instead of deploying."""
    from repro.dataplane import UnknownCodecError, get_codec, list_codecs

    with pytest.raises(UnknownCodecError) as ei:
        get_codec("identty")
    assert "identity" in str(ei.value)  # did-you-mean
    assert "int8" in str(ei.value)  # registered names listed
    assert list_codecs()[0] == "identity"  # default first

    issues = _demo_spec(codec="identty").validate()
    assert [i.code for i in issues] == ["unknown_codec"]
    assert "identity" in issues[0].message
    with pytest.raises(InfeasibleSpecError, match="unknown_codec"):
        deploy(_demo_spec(codec="identty"))


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        register_strategy("placer", "color_coding")(lambda: None)


def test_strategy_table_covers_all_kinds():
    rows = strategy_table()
    kinds = {r["kind"] for r in rows}
    assert kinds == {"partitioner", "placer", "joint"}
    assert sum(1 for r in rows if r["default"] == "yes") == 3


# ---------------------------------------------------------------------------
# Planner parity with the pre-refactor Dispatcher.configure
# ---------------------------------------------------------------------------

def _old_configure(comm_graph, graph, capacity, n_classes, seed, probe_noise=0.05):
    """The pre-API Dispatcher.configure, inlined verbatim as the oracle."""
    cluster = EdgeCluster(comm_graph)
    rng = np.random.default_rng(seed)
    leader = min(cluster.healthy_ids())
    true = cluster.degraded_comm()
    n = true.n
    noise = rng.lognormal(0.0, probe_noise, size=(n, n))
    noise = np.tril(noise) + np.tril(noise, -1).T
    comm = CommGraph(bw=true.bw * noise, node_capacity=true.node_capacity)
    part = partition_min_bottleneck(
        graph, int(capacity), max_parts=len(cluster.healthy_ids())
    )
    assert part.feasible
    place = place_color_coding(
        part.boundaries,
        [p.param_bytes for p in part.partitions],
        comm,
        n_classes=n_classes,
        seed=int(rng.integers(1 << 31)),
        in_bytes=graph.in_bytes,
        out_bytes=graph.layers[-1].out_bytes,
        dispatcher=leader,
    )
    return part, place


@pytest.mark.parametrize("seed", [3, 7, 11, 23])
def test_planner_default_matches_prerefactor_configure(seed):
    graph = _graph()
    comm = random_cluster(8, CAPACITY, seed=seed)
    part0, place0 = _old_configure(comm, graph, CAPACITY, n_classes=4, seed=seed)

    disp = Dispatcher(
        EdgeCluster(comm), ArtifactStore(tempfile.mkdtemp(prefix="seifer-api-")),
        seed=seed,
    )
    plan = disp.configure(graph, version=0, capacity=CAPACITY)

    assert plan.feasible
    assert plan.partition.cuts == part0.cuts  # exact partition boundaries
    assert plan.partition.boundaries == part0.boundaries
    assert plan.placement.path == place0.path  # exact node path
    assert plan.placement.bottleneck_latency == place0.bottleneck_latency
    assert dict(plan.strategies) == {
        "partitioner": "min_bottleneck", "placer": "color_coding",
    }


def test_explicit_default_names_equal_implicit_defaults():
    graph = _graph()
    comm = random_cluster(8, CAPACITY, seed=5)
    implicit = Planner().plan(graph, comm, capacity=CAPACITY, seed=0)
    explicit = Planner("min_bottleneck", "color_coding").plan(
        graph, comm, capacity=CAPACITY, seed=0
    )
    assert implicit.partition.cuts == explicit.partition.cuts
    assert implicit.placement.path == explicit.placement.path


def test_every_registered_pair_plans_the_demo_model():
    from repro.core.model_zoo import demo_mlp

    graph, _ = demo_mlp(d=32)
    cap = graph.total_param_bytes / 3
    comm = random_cluster(8, cap, seed=3)
    for pname in list_strategies("partitioner"):
        for plname in list_strategies("placer"):
            plan = Planner(pname, plname).plan(
                graph, comm, capacity=cap, max_parts=8, seed=1, dispatcher=0,
            )
            assert plan.feasible, (pname, plname)
            assert np.isfinite(plan.predicted_bottleneck_s), (pname, plname)


def test_joint_strategy_never_worse_than_sequential():
    graph = _graph()
    comm = random_cluster(8, CAPACITY, seed=9)
    seq = Planner(joint="sequential").plan(graph, comm, capacity=CAPACITY, seed=2)
    jnt = Planner(joint="joint").plan(graph, comm, capacity=CAPACITY, seed=2)
    assert seq.feasible and jnt.feasible
    assert (jnt.placement.bottleneck_latency
            <= seq.placement.bottleneck_latency + 1e-12)
    # a joint optimizer REPLACES the pipeline: only it is reported
    assert dict(seq.strategies) == {"joint": "sequential"}


def test_joint_path_honors_max_parts():
    graph = _graph()
    comm = random_cluster(8, CAPACITY, seed=9)
    for name in ("sequential", "joint"):
        plan = Planner(joint=name).plan(
            graph, comm, capacity=CAPACITY, max_parts=3, seed=2
        )
        assert plan.feasible and plan.n_parts <= 3, name


def test_compression_reaches_predicted_throughput():
    """configure() threads the spec's link codec into the plan, so SLO
    checks and metrics() agree with Planner.compile().  The inter-stage
    mesh is the slow link here, so compressing it raises the prediction."""
    from repro.core.model_zoo import demo_mlp

    graph, _ = demo_mlp(d=32)
    bw = np.full((5, 5), 1e4)
    bw[0, :] = bw[:, 0] = 1e9  # fast dispatcher round trip
    np.fill_diagonal(bw, 0.0)
    cap = np.full(5, graph.total_param_bytes / 3.0)
    cap[0] = -1.0
    cluster = ClusterSpec(comm=CommGraph(bw=bw, node_capacity=cap))
    spec1 = _demo_spec(codec="identity", cluster=cluster)
    spec2 = _demo_spec(codec="int8", cluster=cluster)
    d1, d2 = deploy(spec1), deploy(spec2)
    assert d2.plan.predicted_throughput > d1.plan.predicted_throughput
    # same partition: the codec only shrinks wire bytes
    assert d2.plan.partition.cuts == d1.plan.partition.cuts
    assert "int8" in d2.plan.codecs and "int8" not in d1.plan.codecs


# ---------------------------------------------------------------------------
# Spec validation: structured infeasibility reasons
# ---------------------------------------------------------------------------

def test_layer_over_capacity_reports_structured_reason():
    huge = chain("huge", [(100 * CAPACITY, 4)] * 4)
    spec = DeploymentSpec(
        model=huge, cluster=ClusterSpec(n_nodes=4, capacity_bytes=CAPACITY),
    )
    issues = spec.validate()
    codes = {i.code for i in issues}
    assert "layer_exceeds_capacity" in codes
    msg = next(i.message for i in issues if i.code == "layer_exceeds_capacity")
    assert "huge.0" in msg and str(100 * CAPACITY) in msg  # names the layer
    with pytest.raises(InfeasibleSpecError, match="layer_exceeds_capacity"):
        deploy(spec)  # the facade refuses up front, no deep stack trace


def test_unknown_strategy_name_fails_validation_with_suggestion():
    spec = _demo_spec(placer="color_codng")
    issues = spec.validate()
    assert any(i.code == "unknown_strategy" for i in issues)
    with pytest.raises(InfeasibleSpecError, match="color_coding"):
        spec.check()


def test_ambiguous_cluster_description_rejected():
    issues = ClusterSpec().validate()  # neither comm nor (n_nodes, capacity)
    assert any(i.code == "ambiguous_cluster" for i in issues)
    comm = random_cluster(4, CAPACITY, seed=0)
    both = ClusterSpec(n_nodes=4, capacity_bytes=CAPACITY, comm=comm)
    assert any(i.code == "ambiguous_cluster" for i in both.validate())
    # partial overlap: comm plus a random-cluster arg that would be ignored
    partial = ClusterSpec(comm=comm, n_nodes=16)
    assert any(i.code == "ambiguous_cluster" for i in partial.validate())
    half = ClusterSpec(n_nodes=4)  # incomplete random description
    assert any(i.code == "ambiguous_cluster" for i in half.validate())
    assert ClusterSpec(comm=comm).validate() == ()


def test_deploy_callable_under_either_import_order():
    """``repro.api.deploy`` names both the facade function and its module;
    whichever object an import order yields must deploy the spec."""
    import repro.api.deploy as deploy_module

    d = deploy_module(_demo_spec())  # the module itself is callable
    assert d.observed().healthy
    from repro.api import deploy as deploy_fn

    assert callable(deploy_fn)


def test_unmeetable_slo_raises_before_deploy():
    spec = _demo_spec(max_bottleneck_s=1e-12)
    with pytest.raises(InfeasibleSpecError, match="slo_bottleneck"):
        Planner.from_spec(spec).compile(spec)


def test_commgraph_shorthand_wraps_into_cluster_spec():
    comm = random_cluster(4, CAPACITY, seed=0)
    spec = DeploymentSpec(model=_graph(), cluster=comm)
    assert isinstance(spec.cluster, ClusterSpec)
    assert spec.validate() == ()


# ---------------------------------------------------------------------------
# deploy(spec): the facade end to end
# ---------------------------------------------------------------------------

def test_deploy_survives_churn_with_same_action_classes():
    """The acceptance scenario: node kill + version bump through the facade
    produce the same reconcile action classes the control-plane tests pin
    (``replace`` for NodeFailed, ``redeploy`` for VersionBumped)."""
    d = deploy(_demo_spec())
    n = 20
    for _ in range(n):
        d.submit(jnp.ones((32,)) * 0.1)
    killed = False
    while d.loop.backlog or d.control.pending:
        if not killed and len(d.loop.completed) >= n // 2:
            d.inject(NodeFailed(d.control.pipeline.pods[1].node_id))
            killed = True
        d.step()
    assert killed
    assert len(d.loop.completed) == n and len(d.loop.failed) == 0

    d.store.publish(1)
    assert d.poll_model_updates()
    for _ in range(4):
        d.submit(jnp.ones((32,)) * 0.1)
    d.drain()
    assert len(d.loop.completed) == n + 4 and len(d.loop.failed) == 0

    kinds = [a.kind for a in d.control.history]
    assert "replace" in kinds and "redeploy" in kinds
    m = d.metrics()
    assert m["version"] == 1 and m["generation"] == 0 and m["healthy"]


def test_deploy_metrics_reports_predicted_and_observed():
    d = deploy(_demo_spec())
    m = d.metrics()
    assert m["strategies"] == {
        "partitioner": "min_bottleneck", "placer": "color_coding",
    }
    assert m["predicted_bottleneck_s"] > 0
    assert np.isfinite(m["bottleneck_latency_s"])
    assert m["serving"]["completed"] == 0


def test_replan_swaps_strategy_on_live_deployment():
    d = deploy(_demo_spec())
    gen0 = d.observed().generation
    plan = d.replan(placer="greedy")
    assert dict(plan.strategies)["placer"] == "greedy"
    assert d.observed().generation == gen0  # no cluster restart
    assert d.observed().healthy
    d.submit(jnp.ones((32,)) * 0.1)
    assert len(d.drain()) == 1


def test_replan_pipeline_strategy_drops_joint():
    """Naming a placer on a joint-optimized deployment must actually swap
    the placement algorithm, not silently keep the joint optimizer."""
    d = deploy(_demo_spec(joint="sequential"))
    assert dict(d.plan.strategies) == {"joint": "sequential"}
    plan = d.replan(placer="greedy")
    assert dict(plan.strategies) == {
        "partitioner": "min_bottleneck", "placer": "greedy",
    }
    assert d.control.planner.joint is None
    # and back to a joint optimizer by naming one
    plan = d.replan(joint="joint")
    assert dict(plan.strategies) == {"joint": "joint"}


def test_infeasible_replan_keeps_running_pipeline_and_planner():
    d = deploy(_demo_spec())
    path0 = list(d.observed().path)
    placer0 = d.control.planner.placer.name
    # an unsatisfiable strategy: exhaustive partitioner is fine, but force
    # infeasibility by shrinking the desired capacity below any single layer
    d.control.desired.capacity = 1.0
    with pytest.raises(RuntimeError):
        d.replan(placer="greedy")
    assert d.control.planner.placer.name == placer0  # planner rolled back
    assert list(d.observed().path) == path0  # pipeline untouched
    assert d.observed().healthy


def test_plan_tracks_replacement_after_node_failure():
    """d.plan must describe what is DEPLOYED: after a NodeFailed recovery
    the recorded path excludes the dead node and matches observed state."""
    d = deploy(_demo_spec())
    victim = d.control.pipeline.pods[1].node_id
    d.inject(NodeFailed(victim))
    d.reconcile()
    assert victim not in d.plan.path
    assert list(d.plan.path) == list(d.observed().path)
    assert np.isfinite(d.plan.predicted_bottleneck_s)


def test_planner_with_explicit_n_classes_conflict_raises():
    cluster = EdgeCluster(random_cluster(4, CAPACITY, seed=0))
    store = ArtifactStore(tempfile.mkdtemp(prefix="seifer-api-"))
    with pytest.raises(ValueError, match="n_classes"):
        Dispatcher(cluster, store, planner=Planner(), n_classes=8)
    # planner alone, or n_classes alone, are both fine
    Dispatcher(cluster, store, planner=Planner(n_classes=8))
    Dispatcher(cluster, store, n_classes=8)


def test_wrong_model_type_gets_bad_model_issue():
    spec = DeploymentSpec(
        model=123, cluster=ClusterSpec(n_nodes=4, capacity_bytes=CAPACITY),
    )
    issues = spec.validate()
    assert any(i.code == "bad_model" for i in issues)
    assert not any(i.code == "unknown_model" for i in issues)


def test_passthrough_executor_for_zoo_models():
    """CNN zoo graphs have no executable weights: serving still works in
    timing-only mode via the pass-through executor."""
    graph = chain("toy", [(CAPACITY // 2, 64)] * 4, in_bytes=64)
    spec = DeploymentSpec(
        model=graph, cluster=ClusterSpec(n_nodes=6, capacity_bytes=CAPACITY),
    )
    d = deploy(spec)
    d.submit(jnp.ones((4,)))
    (req,) = d.drain()
    assert req.done
    assert d.loop.clock_s > 0  # simulated link time still advances


def test_demo_transformer_kernel_path_e2e():
    """Real compute through the serving engine: demo_transformer stages run
    flash attention, int8 hops hand EncodedActivations to the fused
    dequant-matmul handler, and the Pallas (interpret) deployment reproduces
    the reference deployment's outputs."""
    from repro.core.model_zoo import demo_transformer

    x = np.asarray(jnp.ones((256, 32)) * 0.1)
    results = {}
    for use_pallas in (False, True):
        graph, executor_for_version = demo_transformer(
            use_pallas=use_pallas, interpret=use_pallas)
        spec = DeploymentSpec(
            model=graph,
            executor_for_version=executor_for_version,
            cluster=ClusterSpec(n_nodes=6,
                                capacity_bytes=graph.total_param_bytes / 2.5,
                                seed=5),
            codec="int8",
            seed=3,
            use_pallas=use_pallas,
            interpret=use_pallas,
        )
        d = deploy(spec)
        # the fused fast path is live: the executor advertises the int8
        # handler and the planner put int8 on the wire
        assert "int8" in d.control.pipeline.executor.fused_codecs
        assert "int8" in d.plan.codecs
        assert len(d.control.pipeline.pods) >= 2  # a real multi-stage pipe
        d.submit(jnp.asarray(x))
        (req,) = d.drain()
        assert req.done
        results[use_pallas] = np.asarray(req.result)
    assert results[False].shape == (256, 32)
    np.testing.assert_allclose(results[True], results[False],
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Open-loop serving spec surface (traces, SLO classes, batching, autoscale)
# ---------------------------------------------------------------------------

def _codes(spec):
    return {i.code for i in spec.validate()}


def test_bad_batching_and_admission_codes():
    from repro.api import ArrivalSpec  # noqa: F401  (surface check)

    assert "bad_batching" in _codes(_demo_spec(max_batch=0))
    assert "bad_batching" in _codes(_demo_spec(admission_depth=-1))
    assert "bad_batching" not in _codes(_demo_spec(max_batch=8,
                                                   admission_depth=64))


def test_slo_class_validation_codes():
    from repro.api import SLOClass

    bad = _demo_spec(slo_classes=(SLOClass("gold", target_latency_s=-1.0),))
    assert "bad_slo_class" in _codes(bad)
    dup = _demo_spec(slo_classes=(SLOClass("a"), SLOClass("a")))
    assert "bad_slo_class" in _codes(dup)
    ok = _demo_spec(slo_classes=(SLOClass("gold", priority=1,
                                          target_latency_s=0.5),
                                 SLOClass("std")))
    assert "bad_slo_class" not in _codes(ok)
    assert ok.class_priority() == {"gold": 1, "std": 0}
    assert ok.class_targets() == {"gold": 0.5, "std": None}


def test_arrival_spec_validation_codes():
    from repro.api import ArrivalSpec

    unknown = _demo_spec(arrival=ArrivalSpec(trace="poison"))
    assert "unknown_trace" in _codes(unknown)
    assert "bad_arrival" in _codes(_demo_spec(arrival=ArrivalSpec(rate=0.0)))
    assert "bad_arrival" in _codes(
        _demo_spec(arrival=ArrivalSpec(duration_s=-1.0)))
    assert not {"unknown_trace", "bad_arrival"} & _codes(
        _demo_spec(arrival=ArrivalSpec(trace="bursty", rate=50.0,
                                       duration_s=2.0)))


def test_autoscale_spec_validation_codes():
    from repro.api import AutoscaleSpec

    assert "bad_autoscale" in _codes(
        _demo_spec(autoscale=AutoscaleSpec(min_replicas=0)))
    assert "bad_autoscale" in _codes(
        _demo_spec(autoscale=AutoscaleSpec(backlog_high=2.0, backlog_low=4.0)))
    # autoscaling owns the replica count: an explicit replicas=N conflicts
    assert "bad_autoscale" in _codes(
        _demo_spec(replicas=2, autoscale=AutoscaleSpec()))
    # ``autoscale=True`` sugar coerces to the default policy
    sugar = _demo_spec(autoscale=True)
    assert isinstance(sugar.autoscale, AutoscaleSpec)
    assert "bad_autoscale" not in _codes(sugar)


def test_autoscale_min_replicas_infeasible_at_deploy():
    from repro.api import AutoscaleSpec

    spec = _demo_spec(autoscale=AutoscaleSpec(min_replicas=64))
    with pytest.raises(InfeasibleSpecError) as ei:
        deploy(spec)
    assert any(i.code == "infeasible_replicas" for i in ei.value.issues)
