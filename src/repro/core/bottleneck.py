"""Bottleneck-latency / throughput metrics (SEIFER Sec. 2.2-1a).

Latency of a link = bytes transferred / bandwidth.  The *bottleneck latency*
of an inference pipeline is the maximum link latency; pipeline throughput is
its reciprocal.  The extended metric additionally accounts for per-stage
compute time (used when mapping placements onto TPU pods, where stage compute
can dominate the link): steady-state pipeline period = max over all stage
compute times and link latencies.

What each link carries and costs (endpoints, bytes, codec, window) is the
hop model of ``repro.dataplane.hops``; this module adds the stage times and
the pipeline-level scores on top of it.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.core.graph import Partition, boundary_bytes
from repro.core.placement import CommGraph
from repro.dataplane.hops import build_hops, hop_bytes, node_flops


@dataclasses.dataclass(frozen=True)
class PipelineMetrics:
    bottleneck_latency: float  # s, max link latency (paper metric)
    pipeline_period: float  # s, max(link latency, stage compute) (extended)
    end_to_end_latency: float  # s, sum of stage compute + link latencies
    throughput: float  # 1 / bottleneck_latency (paper)
    effective_throughput: float  # 1 / pipeline_period (extended)


def stage_times(
    partitions: Sequence[Partition],
    path: Sequence[int],
    flops_per_node: float | Sequence[float] | None = None,
) -> list[float]:
    """Stage i's service time, ``partition.flops / flops_per_node[path[i]]``
    (0 when flops are unmodelled)."""
    compute = []
    for part, node in zip(partitions, path):
        f = node_flops(flops_per_node, node)
        compute.append(part.flops / f if f > 0 else 0.0)
    return compute


def service_times(
    partitions: Sequence[Partition],
    path: Sequence[int],
    bw: np.ndarray,
    *,
    flops_per_node: float | Sequence[float] | None = None,
    in_bytes: float = 0.0,
    out_bytes: float = 0.0,
    dispatcher: int | None = None,
    codecs: Sequence | None = None,
) -> tuple[list[float], list[float]]:
    """The single timing model shared by the discrete-event serving engine,
    the planner's prediction, and the TPU pipeline planner.

    Returns ``(compute_s, link_s)``: ``stage_times`` per stage, and the
    ``seconds`` of each ``dataplane.hops.build_hops`` record, one per hop
    (``len(path) + 1``; dispatcher -> first stage, each stage boundary,
    last stage -> dispatcher).  Colocated endpoints (or zero bytes, or no
    dispatcher) cost 0; ``codecs`` (one per hop, ``None`` = identity)
    charges each link its codec's ``encode + wire + decode`` window.

    The pipeline's steady-state period is ``max(compute_s + link_s)`` --
    every stage and every link is a serial resource, so the bottleneck one
    sets the cadence once the pipe is full.
    """
    links = build_hops(
        hop_bytes(boundary_bytes(partitions), in_bytes, out_bytes),
        path, bw, dispatcher=dispatcher, flops_per_node=flops_per_node,
        codecs=codecs,
    )
    return (stage_times(partitions, path, flops_per_node),
            [link.seconds for link in links])


def evaluate_pipeline(
    partitions: Sequence[Partition],
    path: Sequence[int],
    comm: CommGraph,
    device_flops: float | Sequence[float] | None = None,
    in_bytes: float = 0.0,
    out_bytes: float = 0.0,
    dispatcher: int | None = None,
    codecs: Sequence | None = None,
) -> PipelineMetrics:
    """Score a (partition, placement) pair.

    ``codecs`` (one per hop, see ``service_times``) charges each link with
    its transfer codec's ``encode + compressed transfer + decode`` window
    (paper: ZFP/LZ4; ours: the ``dataplane`` codecs).  ``in_bytes``
    / ``out_bytes`` charge the dispatcher round-trip hops when
    ``dispatcher`` is given (colocation costs nothing).
    """
    if len(path) != len(partitions):
        raise ValueError("path length != number of partitions")
    compute, hops = service_times(
        partitions, path, comm.bw,
        flops_per_node=device_flops,
        in_bytes=in_bytes if dispatcher is not None else 0.0,
        out_bytes=out_bytes if dispatcher is not None else 0.0,
        dispatcher=dispatcher,
        codecs=codecs,
    )
    lats = [h for h in hops if h > 0]
    bottleneck = max(lats, default=0.0)
    period = max([bottleneck] + compute)
    e2e = sum(compute) + sum(l for l in lats if np.isfinite(l))
    return PipelineMetrics(
        bottleneck_latency=float(bottleneck),
        pipeline_period=float(period),
        end_to_end_latency=float(e2e),
        throughput=0.0 if bottleneck == float("inf") else (float("inf") if bottleneck == 0 else 1.0 / bottleneck),
        effective_throughput=0.0 if period == float("inf") else (float("inf") if period == 0 else 1.0 / period),
    )
