"""Serving throughput under churn, driven through the ``deploy(spec)`` facade.

The scenario DEFER and the joint partition/placement literature use as the
benchmark: a continuous request stream over a re-plannable pipeline, with
disturbances injected **mid-stream**:

  phase 1  steady-state serving (baseline)
  phase 2  a node hosting a partition is killed mid-phase (``NodeFailed``)
  phase 3  steady-state after recovery
  phase 4  a new model version is published mid-phase (``VersionBumped``
           via the watch container's ``poll_events``)
  phase 5  steady-state on the new version

Reported per phase: completed requests, simulated window seconds, and
throughput (req/s).  Recovery is demonstrated by phase-3 and phase-5
throughput returning to within a small factor of phase 1.  All convergence
goes through ``Deployment.inject`` + the serving loop's reconcile -- no
manual ``Dispatcher.recover()``-style calls.  Serving runs through the
pipelined discrete-event engine, so recovery cost includes requeueing
exactly the microbatches resident on the affected stages.  The
partition/placement strategies are registry names, so the same scenario
measures any pair:

  PYTHONPATH=src python -m benchmarks.churn_throughput [--smoke]
      [--partitioner NAME] [--placer NAME]
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.cluster import NodeFailed
from repro.core.model_zoo import demo_mlp

from benchmarks.common import save, table

ARTIFACT = "churn_throughput"  # results/BENCH_churn_throughput.json

D = 32


def _serve_phase(dep, name, n_requests, inject=None):
    """Admit n requests, step to completion; fire ``inject`` mid-phase."""
    loop = dep.loop
    clock0, done0 = loop.clock_s, len(loop.completed)
    for _ in range(n_requests):
        dep.submit(jnp.ones((D,)) * 0.1)
    fired = inject is None
    while loop.backlog or dep.control.pending:
        if not fired and len(loop.completed) - done0 >= n_requests // 2:
            inject()
            fired = True
        dep.step()
    window_s = loop.clock_s - clock0
    done = len(loop.completed) - done0
    return {
        "phase": name,
        "requests": done,
        "window_s": window_s,
        "throughput": done / window_s if window_s > 0 else 0.0,
    }


def run(
    per_phase: int = 40,
    microbatch: int = 4,
    n_nodes: int = 8,
    seed: int = 0,
    partitioner: str | None = None,
    placer: str | None = None,
) -> dict:
    graph, executor_for_version = demo_mlp(d=D)
    spec = DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        cluster=ClusterSpec(
            n_nodes=n_nodes, capacity_bytes=graph.total_param_bytes / 3,
            seed=seed + 3,
        ),
        partitioner=partitioner,
        placer=placer,
        seed=seed,
        microbatch=microbatch,
    )
    dep = deploy(spec)
    strategies = dict(dep.plan.strategies)

    def kill_node():
        pods = dep.control.pipeline.pods
        victim = pods[1 if len(pods) > 1 else 0].node_id
        print(f"  [mid-stream] NodeFailed({victim})")
        dep.inject(NodeFailed(victim))

    def bump_version():
        print("  [mid-stream] store publishes v1 -> VersionBumped")
        dep.store.publish(1)
        dep.poll_model_updates()

    rows = [
        _serve_phase(dep, "steady-v0", per_phase),
        _serve_phase(dep, "node-kill", per_phase, inject=kill_node),
        _serve_phase(dep, "recovered", per_phase),
        _serve_phase(dep, "version-bump", per_phase, inject=bump_version),
        _serve_phase(dep, "steady-v1", per_phase),
    ]
    base = rows[0]["throughput"]
    for r in rows:
        r["vs_baseline"] = r["throughput"] / base

    m = dep.metrics()
    actions = [(a.kind, a.detail) for a in dep.control.history]
    payload = {
        "rows": rows,
        "strategies": strategies,
        "serving_mode": m["serving"]["mode"],
        "stages": m["serving"].get("stages", []),
        "requeued_microbatches": m["serving"].get("requeued_microbatches", 0),
        "actions": actions,
        "bottleneck_latencies": {
            "predicted_s": m["predicted_bottleneck_s"],
            "observed_s": m["bottleneck_latency_s"],
        },
        "final_state": {
            "version": m["version"],
            "generation": m["generation"],
            "path": m["path"],
            "healthy": m["healthy"],
        },
        "lost_requests": m["serving"]["failed"],
        "per_phase": per_phase,
        "microbatch": microbatch,
    }
    save(ARTIFACT, payload)
    print(table(rows, ["phase", "requests", "window_s", "throughput", "vs_baseline"],
                f"Serving throughput under churn ({strategies})"))
    print(f"reconcile actions: {[k for k, _ in actions]}")
    print(f"final: v{m['version']}, generation {m['generation']}, "
          f"path {m['path']}, lost requests: {m['serving']['failed']}")
    assert m["serving"]["failed"] == 0, "requests were lost across recovery"
    assert rows[2]["throughput"] > 0.5 * base, "throughput did not recover after node kill"
    assert rows[4]["throughput"] > 0.5 * base, "throughput did not recover after version bump"
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny run for CI")
    ap.add_argument("--per-phase", type=int, default=None)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--partitioner", default=None)
    ap.add_argument("--placer", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    per_phase = args.per_phase if args.per_phase is not None else (8 if args.smoke else 40)
    run(per_phase=per_phase, microbatch=args.microbatch, seed=args.seed,
        partitioner=args.partitioner, placer=args.placer)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
