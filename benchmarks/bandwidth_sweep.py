"""Codec throughput vs link bandwidth: the data plane's reason to exist.

SEIFER pipelines on edge networks are link-bound -- the inter-partition
activation transfer sets the bottleneck period -- and DEFER (the companion
paper) shows lossy activation compression restores throughput.  This sweep
MEASURES that: a demo_mlp pipeline is served through the discrete-event
engine over a cluster whose *inter-node* mesh bandwidth is swept across four
decades (the dispatcher's own links stay fast, so the constrained resource
is exactly the inter-stage activation path), once per registered codec plus
``codec="auto"``.

Asserted claims (the PR's acceptance criteria):

  * ``int8`` >= ``identity`` throughput on the most constrained link;
  * ``auto`` picks a compressing codec there and improves >= 1.5x over
    ``identity``;
  * engine-measured steady-state throughput is within 5% of
    ``Plan.predicted_throughput`` for EVERY codec at EVERY bandwidth (the
    engine and the planner share ``core.bottleneck.service_times``).

  PYTHONPATH=src python -m benchmarks.bandwidth_sweep [--requests N]
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.core.model_zoo import demo_mlp
from repro.core.placement import CommGraph
from repro.dataplane import list_codecs

from benchmarks.common import save, table

ARTIFACT = "bandwidth_sweep"  # results/BENCH_bandwidth_sweep.json

WIDTH = 32  # demo_mlp width; boundaries carry 16 * 32 * 4 = 2048 B
HOSTING = 4  # one partition per hosting node (capacity = total / 3)
DISPATCHER_BW = 1e9  # node 0's links stay fast: the mesh is the bottleneck
BANDWIDTHS = (1e4, 1e5, 1e6, 1e7)  # bytes/s across the hosting mesh


def _cluster(mesh_bw: float) -> CommGraph:
    """Star-plus-mesh: fast dispatcher links, ``mesh_bw`` everywhere else."""
    n = HOSTING + 1
    bw = np.full((n, n), float(mesh_bw))
    bw[0, :] = bw[:, 0] = DISPATCHER_BW
    np.fill_diagonal(bw, 0.0)
    graph, _ = demo_mlp(d=WIDTH)
    cap = np.full(n, graph.total_param_bytes / 3.0)
    cap[0] = -1.0  # dispatcher hosts no partition
    return CommGraph(bw=bw, node_capacity=cap)


def _measure(codec: str, mesh_bw: float, requests: int, seed: int) -> dict:
    graph, executor_for_version = demo_mlp(d=WIDTH)
    dep = deploy(DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        cluster=ClusterSpec(comm=_cluster(mesh_bw)),
        codec=codec,
        seed=seed,
        microbatch=1,  # measured requests/s == predicted microbatch rate
    ))
    for _ in range(requests):
        dep.submit(jnp.ones((WIDTH,)) * 0.1)
    dep.drain()
    assert len(dep.loop.failed) == 0
    assert len(dep.loop.completed) == requests
    measured = float(dep.loop.steady_state_throughput())
    predicted = float(dep.plan.predicted_throughput)
    links = [ln for ln in dep.loop.metrics()["links"]
             if ln["raw_bytes"] > 0 and 0 < ln["hop"] < len(dep.plan.path)]
    return {
        "bandwidth": mesh_bw,
        "codec": codec,
        "link_codecs": "|".join(dep.plan.codecs),
        "predicted": predicted,
        "measured": measured,
        "vs_predicted": measured / predicted if predicted > 0 else 0.0,
        "compression_x": (
            float(np.mean([ln["compression_x"] for ln in links]))
            if links else 1.0
        ),
    }


def run(requests: int = 48, seed: int = 0) -> dict:
    codecs = (*list_codecs(), "auto")
    rows = [
        _measure(codec, bw, requests, seed)
        for bw in BANDWIDTHS
        for codec in codecs
    ]
    by = {(r["bandwidth"], r["codec"]): r for r in rows}
    slow = min(BANDWIDTHS)
    ident, int8 = by[(slow, "identity")], by[(slow, "int8")]
    auto = by[(slow, "auto")]
    claims = {
        "int8_vs_identity_at_min_bw": int8["measured"] / ident["measured"],
        "auto_vs_identity_at_min_bw": auto["measured"] / ident["measured"],
        "auto_codecs_at_min_bw": auto["link_codecs"],
        "worst_vs_predicted": min(r["vs_predicted"] for r in rows),
        "best_vs_predicted": max(r["vs_predicted"] for r in rows),
    }
    payload = {
        "rows": rows,
        "claims": claims,
        "model": f"demo_mlp(d={WIDTH})",
        "requests": requests,
        "bandwidths": list(BANDWIDTHS),
        "serving": {"engine": "pipelined discrete-event",
                    "dispatcher_bw": DISPATCHER_BW},
    }
    save(ARTIFACT, payload)
    print(table(rows, ["bandwidth", "codec", "predicted", "measured",
                       "vs_predicted", "compression_x"],
                "Pipelined throughput per transfer codec vs mesh bandwidth"))
    print(f"claims: {claims}")
    assert claims["int8_vs_identity_at_min_bw"] >= 1.0, (
        f"int8 must not lose to identity on the constrained mesh, got "
        f"{claims['int8_vs_identity_at_min_bw']:.2f}x"
    )
    assert claims["auto_vs_identity_at_min_bw"] >= 1.5, (
        f"codec='auto' must beat identity >= 1.5x on the constrained mesh, "
        f"got {claims['auto_vs_identity_at_min_bw']:.2f}x"
    )
    assert any(c not in ("identity",) for c in
               auto["link_codecs"].split("|")[1:-1]), (
        "auto kept every inter-stage link uncompressed on a link-bound cluster"
    )
    assert 0.95 <= claims["worst_vs_predicted"], claims
    assert claims["best_vs_predicted"] <= 1.05, claims
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(requests=args.requests, seed=args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
