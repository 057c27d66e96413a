from repro.cluster.autoscale import Autoscaler, ScaleEvent
from repro.cluster.controlplane import (
    ControlPlane,
    DesiredState,
    ObservedState,
    ReconcileAction,
    ReplicaSet,
)
from repro.cluster.dispatcher import Dispatcher, PlacementInfeasible
from repro.cluster.events import (
    ClusterEvent,
    LinkDegraded,
    NodeFailed,
    NodeJoined,
    VersionBumped,
)
from repro.cluster.engine import (
    Microbatch,
    PipelinedServingLoop,
    ReplicatedServingLoop,
    StageState,
)
from repro.cluster.lifecycle import (
    EdgeCluster,
    InferencePipeline,
    Node,
    PipelineDegraded,
    Pod,
)
from repro.cluster.serving import Request, latency_report, latency_stats
from repro.cluster.store import ArtifactStore
from repro.cluster.watch import ModelWatcher

__all__ = [
    "ArtifactStore",
    "Autoscaler",
    "ClusterEvent",
    "ControlPlane",
    "DesiredState",
    "Dispatcher",
    "EdgeCluster",
    "InferencePipeline",
    "LinkDegraded",
    "Microbatch",
    "ModelWatcher",
    "Node",
    "NodeFailed",
    "NodeJoined",
    "ObservedState",
    "PipelineDegraded",
    "PipelinedServingLoop",
    "PlacementInfeasible",
    "Pod",
    "ReconcileAction",
    "ReplicaSet",
    "ReplicatedServingLoop",
    "Request",
    "ScaleEvent",
    "StageState",
    "VersionBumped",
    "latency_report",
    "latency_stats",
]
