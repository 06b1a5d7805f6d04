"""Execution-plan layer — the one device engine behind batch and streaming.

``KeySpace`` × ``WindowSpec`` × ``ReduceSpec`` describe a job;
``ExecutionPlan.compile`` lowers it to a backend (``vmap`` simulated
workers or ``shard_map`` over a real mesh axis).  ``core.mapreduce`` and
``streaming.coordinator`` are thin façades over this package; new modes
(sessions, joins, top-k) should be new plan variants, not new engines.

Layout: ``plan`` (the declarative vocabulary + compiled plan objects),
``stages`` (pure SPMD stage bodies: shuffle, window fan-out, key hashing,
group buffers), ``compile`` (backend lowering, the one ``shard_map``
call site and the compile-cache helper).
"""

from .compile import lower, make_shard_map
from .plan import (CompiledBatchPlan, CompiledStreamAggregate,
                   CompiledStreamGroup, ExecutionPlan, KeySpace, ReduceSpec,
                   WindowSpec, streaming_record_map)
from .stages import (ShuffleStats, device_hash, fold_key24, host_bucket,
                     segment_reduce, top_k_buckets)

__all__ = [
    "ExecutionPlan", "KeySpace", "ReduceSpec", "WindowSpec",
    "CompiledBatchPlan", "CompiledStreamAggregate", "CompiledStreamGroup",
    "streaming_record_map", "lower", "make_shard_map", "ShuffleStats",
    "device_hash", "fold_key24", "host_bucket", "segment_reduce",
    "top_k_buckets",
]
