"""The paper's headline claim, measured EMPIRICALLY: "improve the inference
pipeline throughput by 200% by utilizing sufficient numbers of
resource-constrained nodes."

Earlier revisions of this benchmark reported the *analytic* placement
throughput (1/bottleneck).  This one actually serves a request stream per
cluster size through the ``deploy(spec)`` facade and compares it with
synchronous execution:

  * ``sync``      -- one microbatch traverses the whole chain before the next
    is admitted, so throughput decays with pipeline depth: ``microbatch /
    end_to_end_latency`` of the deployed plan (stage and link times summed
    over the same hop records the engine runs on);
  * ``pipelined`` -- the discrete-event engine, measured: every partition
    works on a different microbatch, so throughput holds at the bottleneck
    stage's rate (the paper's Fig. 5 shape: ~flat in depth).

Reported per cluster size: partition count, the Planner's predicted
bottleneck throughput, the measured pipelined rate, the synchronous rate,
and the speedup.  The run asserts the paper's claim: at >= 8 partitions the
pipelined engine delivers >= 2x synchronous execution.

  PYTHONPATH=src python -m benchmarks.throughput_scaling [--requests N]
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.core.bottleneck import evaluate_pipeline
from repro.core.graph import Layer, LayerGraph

from benchmarks.common import save, table

ARTIFACT = "throughput_scaling"  # results/BENCH_throughput_scaling.json

N_LAYERS = 24
PARAM_BYTES = 1_000_000  # per layer (int8-quantized weights)
ACT_BYTES = 1_000_000  # per boundary activation
FLOPS = 2_000_000  # per layer
NODE_COUNTS = (3, 4, 6, 8, 10, 12)


def _graph() -> LayerGraph:
    layers = tuple(
        Layer(f"l{i}", param_bytes=PARAM_BYTES, out_bytes=ACT_BYTES, flops=FLOPS)
        for i in range(N_LAYERS)
    )
    return LayerGraph("synth24", layers, in_bytes=ACT_BYTES // 4)


def _measure(spec: DeploymentSpec, requests: int) -> tuple[float, float, dict]:
    """(measured pipelined rate, synchronous rate, plan summary)."""
    dep = deploy(spec)
    for _ in range(requests):
        dep.submit(jnp.ones((4,)))
    dep.drain()
    assert len(dep.loop.failed) == 0
    assert len(dep.loop.completed) == requests
    control = dep.control
    pipe, disp = control.pipeline, control.dispatcher
    graph = control.desired.graph
    e2e = evaluate_pipeline(
        [p.partition for p in pipe.pods], pipe.path(), disp.probed,
        device_flops=disp.node_flops(), in_bytes=graph.in_bytes,
        out_bytes=graph.layers[-1].out_bytes, dispatcher=disp.leader,
        codecs=dep.plan.codecs,
    ).end_to_end_latency
    return (float(dep.loop.steady_state_throughput()),
            dep.loop.microbatch / e2e, dep.plan.summary())


def run(requests: int = 96, seed: int = 0) -> dict:
    graph = _graph()
    rows = []
    for n in NODE_COUNTS:
        # smallest per-node capacity that still packs the chain into <= n
        # contiguous parts (ceil division), so partition count tracks n
        layers_per_part = -(-N_LAYERS // n)
        capacity = layers_per_part * PARAM_BYTES * 1.05
        base = dict(
            model=graph,
            cluster=ClusterSpec(n_nodes=n, capacity_bytes=capacity, seed=seed + 3),
            capacity=capacity,
            seed=seed,
            microbatch=1,
        )
        pipe_rate, sync_rate, plan = _measure(DeploymentSpec(**base), requests)
        predicted = float(plan["predicted_throughput"])
        rows.append({
            "nodes": n,
            "parts": len(plan["path"]),
            "predicted": predicted,
            "pipelined": pipe_rate,
            "sync": sync_rate,
            "speedup_x": pipe_rate / sync_rate if sync_rate > 0 else 0.0,
            "vs_predicted": pipe_rate / predicted if predicted > 0 else 0.0,
        })
    deep = [r for r in rows if r["parts"] >= 8]
    base_tp = rows[0]["pipelined"]
    claims = {
        # the paper's 200% improvement: pipelined vs synchronous execution
        "max_speedup_x": max(r["speedup_x"] for r in rows),
        "speedup_at_8plus_parts_x": min(r["speedup_x"] for r in deep) if deep else 0.0,
        # Fig. 5 shape: pipelined throughput tracks the bottleneck rate, it
        # does not decay with partition count the way synchronous execution does
        "pipelined_depth_ratio": min(r["pipelined"] for r in rows) / base_tp,
        "sync_depth_ratio": min(r["sync"] for r in rows) / rows[0]["sync"],
    }
    payload = {
        "rows": rows,
        "claims": claims,
        "model": graph.name,
        "requests": requests,
        "serving": {"engine": "pipelined discrete-event",
                    "baseline": "sync: microbatch / plan end_to_end_latency"},
    }
    save(ARTIFACT, payload)
    print(table(rows, ["nodes", "parts", "predicted", "pipelined", "sync",
                       "speedup_x", "vs_predicted"],
                "Measured serving throughput vs cluster size (paper: +200%)"))
    print(f"claims: {claims}")
    assert deep, "no configuration reached 8 partitions"
    assert claims["speedup_at_8plus_parts_x"] >= 2.0, (
        f"pipelined engine must be >= 2x synchronous execution at >= 8 "
        f"partitions, got {claims['speedup_at_8plus_parts_x']:.2f}x"
    )
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(requests=args.requests, seed=args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
