"""Data-plane Parameter Service runtime (JAX/SPMD).

plan.py          ServicePlan: compiles the control plane's live
                 tensor->Aggregator assignment into a multi-job FlatPlan
                 (segments keyed by (job_id, tensor_key), job runs padded
                 to block_align) plus cached per-job access structures
                 (payload_index, job_layout); pure numpy.  ShardedPlan:
                 one independently sized shard space per Aggregator
                 (compile_sharded_plan) with cross-shard job layouts.
runtime.py       paper-faithful flat PS runtime: pull = one row gather of
                 the job's owned blocks, push = pack + row scatter,
                 update = block-owned Adam (O(job bytes) per step).
service_runtime.py  ServiceRuntime: one shared flat state for all jobs of
                 a ParameterService, migrated live on every replan.
                 ShardedServiceRuntime: one state PER Aggregator shard
                 space, so fleet size changes what executes.
engine.py        ServiceTickEngine: per-job bounded push queues + futures;
                 each tick drains all pending jobs and applies them in ONE
                 batched pass (single Pallas launch on TPU) under a
                 bounded-staleness (max_staleness) contract.
                 ShardedTickEngine: one independent tick loop per shard
                 space (a hot shard never stalls a cold one).
spans.py         host spans inside the engines (``ps.push``, ``ps.tick``,
                 ...), off by default; ``enable(True)`` records them as
                 ``jax.profiler.TraceAnnotation``s.
autoscaler.py    ElasticScaler: per-shard TickStats -> scale_out/scale_in
                 decisions -- the fleet follows measured load (§3.3.2).
sharding.py      per-tensor sharding rules: the control plane's assignment
                 plan realized as NamedShardings (TP + FSDP "aggregation"
                 placement per tensor).
compression.py   int8 gradient compression with error feedback (push path).
elastic.py       tensor migration / elastic re-mesh via resharding.
"""

from .plan import (
    FlatPlan,
    JobLayout,
    Segment,
    ShardedJobLayout,
    ShardedPlan,
    TensorSpec,
    compile_service_plan,
    compile_sharded_plan,
    plan_from_json,
    plan_migration_bytes,
    plan_padding_waste,
    plan_to_json,
    segment_mask,
    sharded_plan_from_json,
    sharded_plan_to_json,
)

__all__ = [
    "FlatPlan",
    "JobLayout",
    "Segment",
    "ShardedJobLayout",
    "ShardedPlan",
    "TensorSpec",
    "compile_service_plan",
    "compile_sharded_plan",
    "plan_from_json",
    "plan_migration_bytes",
    "plan_padding_waste",
    "plan_to_json",
    "segment_mask",
    "sharded_plan_from_json",
    "sharded_plan_to_json",
]
