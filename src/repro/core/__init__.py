"""AIRES core — the paper's primary contribution in JAX.

  memory_model : Eq. (5)-(7) analytical planning
  robw         : Algorithm 1 row block-wise alignment (+ RoBW-128)
  pipeline     : typed pipeline-plan IR + cost/execute interpreters
  analysis     : static plan analyzer (liveness, races, byte lints)
  scheduler    : Algorithm 2 plan builders (AIRES + baselines)
  spgemm       : AiresSpGEMM public API + chained GCN epoch runner
  calibration  : online per-path bandwidth/latency fitting (cost loop)
  autotune     : schedule knob search over the plan IR
"""
from repro.core.analysis import (
    AnalysisReport,
    Finding,
    PlanAnalysisError,
    RULES,
    analyze_plan,
    diff_path_totals,
    path_byte_totals,
)
from repro.core.memory_model import (
    FeatureSpec,
    MemoryEstimate,
    calc_mem,
    ell_bucket_capacity,
    estimate_output_bytes,
    estimate_resident_bytes,
    plan_memory,
    plan_memory_dense_features,
    plan_memory_spec,
    plan_memory_unified,
    required_bytes,
    segment_budget,
)
from repro.core.pipeline import (
    AllocOp,
    CacheProbeOp,
    ComputeOp,
    CostInterpreter,
    ExecuteInterpreter,
    HostPreprocessOp,
    PhaseSpec,
    PipelinePlan,
    PlanOp,
    PlanValidationError,
    TransferOp,
    modeled_spgemm_seconds,
)
from repro.core.passes import (
    CoalescedPayload,
    EDFOrderingPass,
    PassContext,
    PassPipeline,
    PassReport,
    PlanPass,
    ShardPlacementPass,
    TransferCoalescingPass,
    deadline_order,
    edf_sort,
)
from repro.core.autotune import (
    TunedSchedule,
    autotune_schedule,
    bucket_set_bytes,
    candidate_bucket_sets,
)
from repro.core.calibration import (
    CostCalibrator,
    PathEstimate,
)
from repro.core.robw import (
    RoBWPlan,
    RoBWSegment,
    densify_segment,
    merge_partial_rows,
    naive_partition,
    robw_delta_partition,
    robw_partition,
    robw_transpose_plan,
    segment_ell_widths,
    segments_to_block_ell,
)
from repro.core.scheduler import (
    SCHEDULERS,
    AiresScheduler,
    ETCScheduler,
    MaxMemoryScheduler,
    ScheduleMetrics,
    ScheduleResult,
    UCGScheduler,
)
from repro.core.spgemm import (
    AiresConfig, AiresSpGEMM, BrickPayload, EpochMetrics, UpdateStats,
    gcn_epoch,
)

__all__ = [
    "AnalysisReport", "Finding", "PlanAnalysisError", "RULES",
    "analyze_plan", "diff_path_totals", "path_byte_totals",
    "FeatureSpec", "MemoryEstimate", "calc_mem", "ell_bucket_capacity",
    "estimate_output_bytes", "estimate_resident_bytes", "plan_memory",
    "plan_memory_dense_features", "plan_memory_spec", "plan_memory_unified",
    "required_bytes",
    "segment_budget",
    "RoBWPlan", "RoBWSegment", "densify_segment", "merge_partial_rows",
    "naive_partition", "robw_delta_partition", "robw_partition",
    "robw_transpose_plan", "segment_ell_widths", "segments_to_block_ell",
    "CostCalibrator", "PathEstimate",
    "TunedSchedule", "autotune_schedule", "bucket_set_bytes",
    "candidate_bucket_sets",
    "SCHEDULERS", "AiresScheduler", "ETCScheduler", "MaxMemoryScheduler",
    "ScheduleMetrics", "ScheduleResult", "UCGScheduler",
    "AllocOp", "CacheProbeOp", "ComputeOp", "CostInterpreter",
    "ExecuteInterpreter", "HostPreprocessOp", "PhaseSpec", "PipelinePlan",
    "PlanOp", "PlanValidationError", "TransferOp", "modeled_spgemm_seconds",
    "CoalescedPayload", "EDFOrderingPass", "PassContext", "PassPipeline",
    "PassReport", "PlanPass", "ShardPlacementPass", "TransferCoalescingPass",
    "deadline_order", "edf_sort",
    "AiresConfig", "AiresSpGEMM", "BrickPayload", "EpochMetrics",
    "UpdateStats", "gcn_epoch",
]
