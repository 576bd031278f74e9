"""Serving substrate: the paper-partitioned request batcher and the
continuous-batching workflow engine."""
from .engine import (PartitionedBatcher, ReplicaGroup, WorkflowEngine,
                     row_pgd_step)
from .telemetry import ServeTelemetry, StreamingStat

__all__ = ["PartitionedBatcher", "ReplicaGroup", "WorkflowEngine",
           "row_pgd_step", "ServeTelemetry", "StreamingStat"]
