"""Request records and metrics helpers for the serving engine and routers.

The paper's inference step (Sec. 2.3) is a continuous stream of requests
through the pod chain.  ``cluster.engine.PipelinedServingLoop`` serves that
stream: every partition works on a different microbatch, so throughput is
the bottleneck rate ``1 / max(stage, link time)`` (the paper's
pipeline-parallel model).  This module holds what the engine, the replica
router and the tenancy router share: the ``Request`` record,
``latency_report`` (re-exported from ``obs.stats``) and
``normalize_metrics``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from repro.obs.stats import latency_report, latency_stats, percentile  # noqa: F401 -- re-exported; the single nearest-rank implementation lives in obs.stats


@dataclasses.dataclass
class Request:
    """One admitted inference request (a single sample).

    ``replica`` is stamped by the cluster-wide router when the request is
    dispatched to a pipeline replica (re-stamped if it is re-routed after a
    replica retires); ``None`` under single-pipeline serving.

    ``submitted_s`` is the *arrival* time on the virtual clock: the loop's
    clock at ``submit()``, or the trace timestamp under open-loop
    ``schedule()`` -- so ``completed_s - submitted_s`` is the request's full
    admit-to-complete latency, queueing included.  ``slo_class`` names the
    request's latency class (``None`` = unclassified); ``priority`` orders
    continuous-batch admission (higher first, FIFO within a class).
    ``tenant`` is stamped by the tenancy router under multi-tenant serving
    (``None`` for single-tenant deployments).
    """

    req_id: int
    x: Any
    submitted_s: float
    attempts: int = 0
    completed_s: float | None = None
    result: Any = None
    replica: int | None = None
    slo_class: str | None = None
    priority: int = 0
    tenant: str | None = None

    @property
    def done(self) -> bool:
        return self.completed_s is not None

    @property
    def latency_s(self) -> float | None:
        """Admit-to-complete time on the virtual clock; None while pending."""
        if self.completed_s is None:
            return None
        return self.completed_s - self.submitted_s


def normalize_metrics(payload):
    """Canonical metrics payload: the JSON round-trip identity.

    Every mapping key is coerced to ``str`` (some sub-dicts -- per-replica,
    per-link, per-tenant -- were historically keyed by whatever the
    producer used, so ints and stringified ints could coexist in one
    payload), tuples become lists, and numpy scalars become native Python
    numbers.  Applied once at the metrics facades (``Deployment.metrics``,
    the engines, the tenancy router), so ``json.loads(json.dumps(m)) == m``
    holds for every metrics dict the benchmarks persist.
    """
    if isinstance(payload, dict):
        return {str(k): normalize_metrics(v) for k, v in payload.items()}
    if isinstance(payload, (list, tuple)):
        return [normalize_metrics(v) for v in payload]
    if isinstance(payload, bool) or payload is None:
        return payload
    if isinstance(payload, (int, float, str)):
        return payload
    import numpy as _np

    if isinstance(payload, _np.integer):
        return int(payload)
    if isinstance(payload, _np.floating):
        return float(payload)
    return payload
