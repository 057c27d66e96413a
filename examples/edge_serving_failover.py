"""End-to-end SEIFER lifecycle through the ``deploy(spec)`` facade.

One ``DeploymentSpec`` replaces the old six-object hand-wiring: ``deploy()``
bootstraps (elect -> probe -> partition/place -> deploy), then the
``Deployment`` serves a request stream, absorbs a node failure mid-stream
(reconcile: re-place), an in-place model-version update, a strategy swap on
the LIVE deployment (``replan``), and a node join (full cluster restart) --
every convergence step driven by typed events.

    PYTHONPATH=src python examples/edge_serving_failover.py

Expected output (paths/latencies vary slightly with placement seeds):

    bootstrap: 4 partitions on nodes [4, 3, 5, 2], bottleneck 0.159 ms
    served 8 requests, clock 0.486 ms
    NodeFailed(3) -> [('replace', 're-placed 1 pod(s) off node 3; ...')]
    recovered: path [2, 8, 6, 1], outputs identical: True
    VersionBumped(1) -> [('redeploy', 'in-place redeploy at v1')]
    generation still 0 (no cluster restart on a version bump)
    replan(placer='greedy') -> path [...], still v1, generation 0
    NodeJoined(new node 9) -> [('restart', 'full restart (gen 1) ...')]
    lifecycle complete: v1, generation 1, 0 lost requests
"""

import jax.numpy as jnp

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.cluster import NodeFailed
from repro.core.model_zoo import demo_mlp

# --- the spec: an executable 8-layer tanh-MLP (weights keyed by model
# version, so a VersionBumped redeploy visibly changes the function) on a
# seeded random wireless cluster, int8 codec on every inter-stage link -------
D = 32
graph, executor_for_version = demo_mlp(d=D)
spec = DeploymentSpec(
    model=graph,  # "demo_mlp" (zoo name) works too and brings its own executor
    executor_for_version=executor_for_version,
    cluster=ClusterSpec(
        n_nodes=8, capacity_bytes=graph.total_param_bytes / 3, seed=3,
    ),
    codec="int8",  # blockwise int8 on the inter-stage links
    seed=0,
    microbatch=4,
)

# --- bootstrap: Sec 2.1 init + Sec 2.2 configuration, in one call ------------
d = deploy(spec)
obs = d.observed()
print(f"bootstrap: {len(obs.path)} partitions on nodes {list(obs.path)}, "
      f"bottleneck {obs.bottleneck_latency*1e3:.3f} ms")

# --- inference step (Sec 2.3): request stream through the admission queue ----
for _ in range(8):
    d.submit(jnp.ones((D,)) * 0.1)
d.drain()
y0 = d.loop.completed[0].result
print(f"served {len(d.loop.completed)} requests, clock {d.loop.clock_s*1e3:.3f} ms")

# --- node failure: the reconciler re-places partitions on healthy nodes ------
victim = d.control.pipeline.pods[1].node_id
d.inject(NodeFailed(victim))
actions = d.reconcile()
print(f"NodeFailed({victim}) -> {[(a.kind, a.detail) for a in actions]}")
d.submit(jnp.ones((D,)) * 0.1)
d.drain()
identical = bool(jnp.allclose(y0, d.loop.completed[-1].result))
assert identical, "recovered pipeline must compute identically"
print(f"recovered: path {list(d.observed().path)}, outputs identical: {identical}")

# --- model-version update: watch container emits, reconciler redeploys -------
d.store.publish(1)  # the external model repository pushes v1
d.poll_model_updates()
actions = d.reconcile()
print(f"VersionBumped(1) -> {[(a.kind, a.detail) for a in actions]}")
assert d.control.generation == 0
print("generation still 0 (no cluster restart on a version bump)")

# --- strategy swap on the LIVE deployment: replan, no restart ----------------
plan = d.replan(placer="greedy")
obs = d.observed()
print(f"replan(placer='greedy') -> path {list(obs.path)}, "
      f"still v{obs.version}, generation {obs.generation}")
assert dict(plan.strategies)["placer"] == "greedy"

# --- node join: per the paper this is the one event needing a full restart ---
d.grow_cluster(seed=11)
actions = d.reconcile()
print(f"NodeJoined(new node {d.cluster.n - 1}) -> "
      f"{[(a.kind, a.detail) for a in actions]}")

obs = d.observed()
print(f"lifecycle complete: v{obs.version}, generation {obs.generation}, "
      f"{len(d.loop.failed)} lost requests")
