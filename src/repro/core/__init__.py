"""WUKONG core: decentralized serverless DAG engine (the paper's contribution)."""
from repro.core.api import GraphBuilder, delayed_graph
from repro.core.cache import (
    CacheConfig,
    CacheRegistry,
    CacheStats,
    ExecutorCache,
)
from repro.core.dag import (
    DAG,
    EXPAND_BASE,
    DynamicDAG,
    Expansion,
    ExpansionDelta,
    ExpansionError,
    Task,
    TaskRef,
    expansion_base_key,
)
from repro.core.engine import (
    ENGINES,
    CentralizedConfig,
    EngineConfig,
    JobError,
    JobReport,
    JobSubstrate,
    ParallelInvokerEngine,
    PubSubEngine,
    ServerfulConfig,
    ServerfulEngine,
    StrawmanEngine,
    WukongEngine,
)
from repro.core.faults import (
    FaultConfig,
    FaultInjector,
    FaultStats,
    SimulatedTaskFailure,
)
from repro.core.kvstore import PURGED, CostModel, KVNamespace, ShardedKVStore
from repro.core.optimize import (
    ALL_PASSES,
    NO_PASSES,
    CompiledDAG,
    OptimizeConfig,
    PassStats,
    compile_dag,
)
from repro.core.orchestrator import (
    JobOrchestrator,
    JobRequest,
    OrchestratorConfig,
    OrchestratorCrashed,
    OrchestratorReport,
    Substrate,
    TenantSpec,
    WorkloadConfig,
    generate_workload,
)
from repro.core.schedule import StaticSchedule, generate_static_schedules
from repro.core.simclock import (
    EventClock,
    run_effects,
    simulated_compute,
)
from repro.core.statemachine import (
    ADMITTED,
    CANCELLED,
    COMPLETED,
    CONTROL_NS,
    FAILED,
    PENDING,
    RUNNING,
    TERMINAL_STATES,
    InvalidTransition,
    JobStateMachine,
)
from repro.core.triggers import (
    TRIGGER_NS,
    TRIGGER_SOURCES,
    StreamConfig,
    StreamingReport,
    TriggerBus,
    TriggerRule,
    stream_arrivals,
    stream_source,
)


def __getattr__(name):
    # Lazy re-export of the platform surface (PEP 562): an eager import
    # would close the repro.platform -> repro.core.kvstore ->
    # repro.core.__init__ cycle and break `import repro.platform` in a
    # fresh process.
    if name in ("FaaSPlatform", "PlatformConfig"):
        import repro.platform

        return getattr(repro.platform, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "DAG", "Task", "TaskRef", "GraphBuilder", "delayed_graph",
    "DynamicDAG", "Expansion", "ExpansionDelta", "ExpansionError",
    "EXPAND_BASE", "expansion_base_key",
    "ENGINES", "EngineConfig", "CentralizedConfig", "ServerfulConfig",
    "JobError", "JobReport", "JobSubstrate", "WukongEngine",
    "StrawmanEngine", "PubSubEngine", "ParallelInvokerEngine",
    "ServerfulEngine",
    "FaultConfig", "FaultInjector", "FaultStats", "SimulatedTaskFailure",
    "CacheConfig", "CacheStats", "ExecutorCache", "CacheRegistry",
    "CostModel", "ShardedKVStore", "KVNamespace", "PURGED",
    "TriggerBus", "TriggerRule", "StreamConfig", "StreamingReport",
    "TRIGGER_NS", "TRIGGER_SOURCES", "stream_arrivals", "stream_source",
    "JobOrchestrator", "JobRequest", "OrchestratorConfig",
    "OrchestratorCrashed", "OrchestratorReport", "Substrate", "TenantSpec",
    "WorkloadConfig", "generate_workload",
    "JobStateMachine", "InvalidTransition", "CONTROL_NS",
    "PENDING", "ADMITTED", "RUNNING", "COMPLETED", "FAILED", "CANCELLED",
    "TERMINAL_STATES",
    "StaticSchedule", "generate_static_schedules",
    "OptimizeConfig", "CompiledDAG", "PassStats", "compile_dag",
    "ALL_PASSES", "NO_PASSES",
    "EventClock", "run_effects", "simulated_compute",
    "PlatformConfig", "FaaSPlatform",
]
