"""GriNNder core: structured storage offloading (cache/(re)gather/bypass)."""
from repro.core.counters import Counters
from repro.core.storage import (
    RetryPolicy, StorageCorruptionError, StorageDeadlineError, StorageError,
    StorageFullError, StorageIOQueue, StorageTier, TransientIOError,
)
from repro.core.faults import FaultPolicy, FaultyTier
from repro.core.cache import HostCache
from repro.core.plan import PartitionPlan, WorkUnit, build_plan
from repro.core.engine import SSOEngine
from repro.core.costmodel import (
    TierBandwidths, PAPER_WORKSTATION, modeled_time, ModeledTime,
    gnn_epoch_flops,
)
from repro.core.microbatch import microbatch_grads, build_full_mfg

__all__ = [
    "Counters", "StorageTier", "StorageIOQueue", "HostCache",
    "StorageError", "TransientIOError", "StorageCorruptionError",
    "StorageDeadlineError", "StorageFullError", "RetryPolicy",
    "FaultPolicy", "FaultyTier",
    "PartitionPlan", "WorkUnit", "build_plan", "SSOEngine",
    "TierBandwidths", "PAPER_WORKSTATION", "modeled_time", "ModeledTime",
    "gnn_epoch_flops",
    "microbatch_grads", "build_full_mfg",
]
