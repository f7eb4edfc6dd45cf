"""DaphneSched core: the paper's primary contribution.

Two independent axes (paper §3): work *partitioning* (11 DLS techniques) and
work *assignment* (centralized self-scheduling, or distributed queues with
technique-driven work stealing and 4 victim-selection strategies), plus the
distributed coordinator, the TPU device-schedule adaptation, the
auto-selection extension (the paper's stated future work), the pipeline-DAG
runtime (DESIGN.md §9), the multi-tenant serving runtime (DESIGN.md §10),
the online adaptive-scheduling feedback loop (DESIGN.md §12), the
heterogeneous placement & co-execution layer that splits pipeline DAGs
across the host pool and the device walker (DESIGN.md §13), and the
serving front door — open-loop admission control, same-shape batching,
pool autoscaling — behind the unified Submission surface and string-spec
registry (DESIGN.md §14), and preemptive multi-tenancy — chunk-boundary
checkpoint/preempt/resume with host<->device mid-flight migration and the
deadline-pressure "preemptive" arbiter (DESIGN.md §15).
"""

from .admission import (
    AdmissionController,
    AdmissionDecision,
    AutoscalePolicy,
    BatchPolicy,
    FrontDoor,
    FrontDoorResult,
    MemberOutcome,
    OpenLoopResult,
    TokenBucket,
    batch_signature,
    coalesce_submissions,
    heavy_tailed_trace,
    merge_dags,
    replay_open_loop,
)
from .autotune import (
    DagTuner,
    OnlineTuneResult,
    OnlineTuner,
    default_search_space,
    select_offline,
    select_offline_dag,
    select_offline_device_dag,
    select_offline_hetero,
    select_offline_server,
    tune_online_dag,
    tune_online_hetero,
)
from .hetero import HeteroExecutor, HeteroResult
from .placement import (
    DEVICE,
    HOST,
    SPLIT,
    HeteroCostModel,
    HeteroSimResult,
    Placement,
    StagePlacement,
    TransferEvent,
    TransferModel,
    calibrate_hetero_costs,
    replay_online_hetero,
    select_placement,
    simulate_hetero_dag,
)
from .coordinator import Coordinator, CoordinatorConfig, NodeSched
from .dag import (
    DEP_ELEMENTWISE,
    DEP_FULL,
    DagResult,
    EventLog,
    NullEventLog,
    PipelineDAG,
    PipelineExecutor,
    Stage,
    StageDep,
    StageResult,
    TaskEvent,
)
from .device_schedule import (
    DeviceDagTables,
    assign_chunks,
    build_dag_tables,
    build_dag_tables_cached,
    build_task_table,
    cost_balanced_assignment,
    dag_signature,
    dag_table_cache_stats,
    per_shard_tables,
    rebalance,
    rebalance_dag,
)
from .executor import ExecutionStats, ScheduledExecutor, SchedulerConfig
from .lower import (
    Lowered,
    chain_dag,
    costs_from_sizes,
    fanout_stage,
    measure_stage_costs,
    row_stage,
    run_direct,
)
from .online import (
    SELECTORS,
    ChunkObservation,
    EXP3Selector,
    FeedbackLog,
    OnlineChoice,
    OnlineRound,
    OnlineScheduler,
    StageFeedback,
    UCB1Selector,
    default_hetero_arms,
    default_online_arms,
    replay_online_dag,
)
from .server import (
    ARBITERS,
    Arbiter,
    FairShareArbiter,
    FifoArbiter,
    Job,
    JobResult,
    JobState,
    PipelineServer,
    PriorityArbiter,
    ServerResult,
    ServerTaskEvent,
    job_stage_costs,
    make_arbiter,
)
from .partitioners import (
    PARTITIONERS,
    Partitioner,
    chunk_schedule,
    chunk_sizes,
    first_chunk,
    first_chunk_fn,
    make_partitioner,
)
from .preempt import (
    JobCheckpoint,
    PreemptableStageRun,
    PreemptionEvent,
    PreemptiveArbiter,
    PreemptiveRunner,
    StageCheckpoint,
    migrate_to_device,
    resume_on_host,
    run_device_prefix,
)
from .queues import (
    QUEUE_LAYOUTS,
    CentralizedQueue,
    DistributedQueues,
    SlotCentralizedQueue,
    SlotDistributedQueues,
)
from .simulator import (
    DagSimResult,
    DagStats,
    ServerSimResult,
    SimOverheads,
    SimResult,
    frozen_dag_makespans,
    simulate,
    simulate_dag,
    simulate_server,
    stats_from_events,
)
from .registry import REGISTRY, make, make_config, make_placement
from .submit import Submission, as_submission
from .telemetry import (
    NULL_TRACER,
    CriticalPathReport,
    MetricsRegistry,
    NullTracer,
    Span,
    Tracer,
    analyze_critical_path,
    as_tracer,
    collect_bandit_metrics,
    collect_cache_metrics,
    collect_compile_metrics,
    collect_queue_metrics,
    collect_server_metrics,
    compile_counts,
    loop_counts,
    validate_chrome_trace,
)
from .task import RangeTask, tasks_from_schedule
from .victim import VICTIM_STRATEGIES, VictimSelector, make_victim_selector

__all__ = [
    "PARTITIONERS", "Partitioner", "chunk_schedule", "chunk_sizes",
    "first_chunk", "first_chunk_fn", "make_partitioner",
    "QUEUE_LAYOUTS", "CentralizedQueue", "DistributedQueues",
    "SlotCentralizedQueue", "SlotDistributedQueues",
    "VICTIM_STRATEGIES", "VictimSelector", "make_victim_selector",
    "RangeTask", "tasks_from_schedule",
    "SchedulerConfig", "ScheduledExecutor", "ExecutionStats",
    "SimOverheads", "SimResult", "simulate", "DagSimResult", "simulate_dag",
    "frozen_dag_makespans", "ServerSimResult", "simulate_server",
    "Lowered", "row_stage", "chain_dag", "fanout_stage", "run_direct",
    "measure_stage_costs", "costs_from_sizes",
    "DEP_FULL", "DEP_ELEMENTWISE", "Stage", "StageDep", "PipelineDAG",
    "PipelineExecutor", "StageResult", "DagResult", "TaskEvent",
    "EventLog", "NullEventLog",
    "Job", "JobState", "JobResult", "ServerResult", "ServerTaskEvent",
    "Arbiter", "FifoArbiter", "PriorityArbiter", "FairShareArbiter",
    "ARBITERS", "make_arbiter", "PipelineServer", "job_stage_costs",
    "Coordinator", "CoordinatorConfig", "NodeSched",
    "build_task_table", "assign_chunks", "per_shard_tables", "rebalance",
    "cost_balanced_assignment",
    "DeviceDagTables", "build_dag_tables", "rebalance_dag",
    "dag_signature", "build_dag_tables_cached", "dag_table_cache_stats",
    "select_offline", "OnlineTuner", "default_search_space",
    "select_offline_dag", "DagTuner", "select_offline_server",
    "select_offline_device_dag",
    "ChunkObservation", "StageFeedback", "FeedbackLog", "OnlineChoice",
    "OnlineRound", "OnlineScheduler", "UCB1Selector", "EXP3Selector",
    "SELECTORS", "default_online_arms", "default_hetero_arms",
    "replay_online_dag", "OnlineTuneResult", "tune_online_dag",
    "DagStats", "stats_from_events",
    "HOST", "DEVICE", "SPLIT", "TransferModel", "HeteroCostModel",
    "StagePlacement", "Placement", "TransferEvent", "HeteroSimResult",
    "calibrate_hetero_costs", "simulate_hetero_dag", "select_placement",
    "replay_online_hetero", "HeteroExecutor", "HeteroResult",
    "select_offline_hetero", "tune_online_hetero",
    "Submission", "as_submission",
    "REGISTRY", "make", "make_config", "make_placement",
    "TokenBucket", "AdmissionDecision", "AdmissionController",
    "batch_signature", "merge_dags", "coalesce_submissions", "BatchPolicy",
    "AutoscalePolicy", "MemberOutcome", "OpenLoopResult", "replay_open_loop",
    "heavy_tailed_trace", "FrontDoor", "FrontDoorResult",
    "StageCheckpoint", "JobCheckpoint", "PreemptableStageRun",
    "PreemptiveRunner", "resume_on_host", "migrate_to_device",
    "run_device_prefix", "PreemptionEvent", "PreemptiveArbiter",
    "Tracer", "NullTracer", "NULL_TRACER", "as_tracer", "Span",
    "MetricsRegistry", "CriticalPathReport", "analyze_critical_path",
    "validate_chrome_trace", "collect_queue_metrics", "collect_cache_metrics",
    "collect_bandit_metrics", "collect_server_metrics",
    "collect_compile_metrics", "compile_counts", "loop_counts",
]
