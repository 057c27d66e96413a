"""Quickstart: declare a deployment spec, compile it into a plan.

The declarative API in one screen: describe the model, the cluster, and the
strategies by NAME (``repro.api.list_strategies`` shows what's registered),
then let the ``Planner`` run SEIFER's two steps -- min-bottleneck
partitioning (Sec. 2.2-1b) and bandwidth-aware placement (Sec. 2.2-1c) --
and score the result.  No cluster machinery; for serving + churn see
``examples/edge_serving_failover.py`` (the ``deploy()`` facade).

    PYTHONPATH=src python examples/quickstart.py

Expected output (exact numbers vary with the cluster seed):

    registered strategies:
      partitioner: min_bottleneck*, exact_k, exhaustive, min_sum, paper_greedy
      placer: color_coding*, greedy, optimal, random
      joint: sequential*, joint
    model: resnet50, 18 layers, 25.5 MB int8 weights
    partitions: 4, cuts at (12, 14, 15), max boundary 0.80 MB
    placement: nodes (2, 3, 5, 1), bottleneck 47.05 ms, throughput 21.3 inf/s
    codec identity: period 1059.40 ms, effective throughput 0.9 inf/s
    codec int8: period 1059.40 ms, effective throughput 0.9 inf/s

(The int8 row matches identity here because this cluster's period is
compute-bound; on a bandwidth-bound cluster, the int8 link codec cuts the
wire time about 4x -- see ``benchmarks/bandwidth_sweep.py``.)
"""

from repro.api import (
    ClusterSpec,
    DeploymentSpec,
    Planner,
    default_strategy,
    list_strategies,
)
from repro.core import evaluate_pipeline
from repro.core.model_zoo import resnet50
from repro.dataplane import assign_link_codecs, hop_bytes

# 0. every algorithm is a named, registered strategy (default marked *)
print("registered strategies:")
for kind in ("partitioner", "placer", "joint"):
    names = [n + "*" if n == default_strategy(kind) else n
             for n in list_strategies(kind)]
    print(f"  {kind}: {', '.join(names)}")

# 1. the spec: model + cluster + strategy names, declared up front
graph = resnet50()
capacity = graph.total_param_bytes / 3  # each node holds ~1/3 of the model
spec = DeploymentSpec(
    model="resnet50",  # zoo name; a LayerGraph works too
    cluster=ClusterSpec(n_nodes=8, capacity_bytes=capacity, seed=0),
    partitioner="min_bottleneck",  # SEIFER step 1 (Sec. 2.2-1b)
    placer="color_coding",         # SEIFER step 2 (Sec. 2.2-1c)
)
print(f"model: {graph.name}, {len(graph)} layers, "
      f"{graph.total_param_bytes/1e6:.1f} MB int8 weights")

# 2. compile: validate the spec, partition, place, predict metrics
plan = Planner.from_spec(spec).compile(spec)
part, place = plan.partition, plan.placement
print(f"partitions: {part.n_parts}, cuts at {part.cuts}, "
      f"max boundary {part.max_cut_bytes/1e6:.2f} MB")
print(f"placement: nodes {place.path}, "
      f"bottleneck {place.bottleneck_latency*1e3:.2f} ms, "
      f"throughput {place.throughput:.1f} inf/s")

# 3. end-to-end metrics, raw links vs the int8 link codec (ZFP/LZ4 on the
#    edge; blockwise int8 here -- see repro.dataplane and kernels/quantize)
comm, _ = spec.cluster.build()
for codec in ("identity", "int8"):
    codecs = assign_link_codecs(hop_bytes(part.boundaries), place.path,
                                comm.bw, codec=codec)
    m = evaluate_pipeline(part.partitions, place.path, comm,
                          device_flops=5e9, codecs=codecs)
    print(f"codec {codec}: period {m.pipeline_period*1e3:.2f} ms, "
          f"effective throughput {m.effective_throughput:.1f} inf/s")
