"""Pinned results of deterministic workloads on the simulation clock.

Each case runs one seeded workload on ``EventClock`` and compares its
projection with literals recorded from a known-good tree: the roots'
values,
``charged_ms`` and simulated seconds to 12 significant digits, the KV,
platform, fault and cache counters, billed USD, journal and fire-key
digests where the workload has them, and the clock's frame steps
(``EventClock.switches``). The headline numbers are pinned as they are;
everything else is pinned through the digest of the whole projection.

A change that moves any simulated quantity, or the number of frame
steps the clock takes, fails here. Results that XLA computes (GEMM
blocks) are checked against NumPy with a tolerance instead, since their
last bits are the CPU's and not the simulation's.
"""
import hashlib
import json
import random

import numpy as np
import pytest

from repro.analysis.divergence import Tracer
from repro.apps import (
    dynamic_tree_reduction_dag,
    dynamic_tree_reduction_expected,
    gemm_dag,
    static_tree_reduction_equivalent,
    tree_reduction_dag,
)
from repro.apps.gemm import _block
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import (
    ALL_PASSES,
    EXPAND_BASE,
    NO_PASSES,
    CacheConfig,
    CostModel,
    DynamicDAG,
    EngineConfig,
    Expansion,
    FaultConfig,
    JobOrchestrator,
    OptimizeConfig,
    OrchestratorConfig,
    StreamConfig,
    Task,
    TaskRef,
    TenantSpec,
    TriggerRule,
    WorkloadConfig,
    WukongEngine,
)
from repro.core.orchestrator import Substrate
from repro.core.simclock import EventClock
from repro.platform import PlatformConfig

# ---------------------------------------------------------------------------
# Projection
# ---------------------------------------------------------------------------


def _canon(x):
    """``x`` as plain JSON: floats to 12 significant digits, arrays and
    tuples as lists, dict keys as strings."""
    if isinstance(x, (bool, int, str)) or x is None:
        return x
    if isinstance(x, (float, np.floating)):
        return float(f"{float(x):.12g}")
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.ndarray):
        return [_canon(v) for v in x.ravel().tolist()]
    if isinstance(x, dict):
        return {str(k): _canon(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_canon(v) for v in x]
    raise TypeError(f"no projection for {type(x).__name__}")


def _digest(x) -> str:
    text = json.dumps(_canon(x), sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _job(report, results=True) -> dict:
    """A JobReport's simulated quantities (host_profile is host time and
    the optimizer's pass report is the compiler's, so neither is here)."""
    out = {
        "wall_s": report.wall_s,
        "charged_ms": report.charged_ms,
        "tasks": report.tasks,
        "executors": report.executors_invoked,
        "kv_stats": report.kv_stats,
        "platform_stats": report.platform_stats,
        "fault_stats": report.fault_stats,
        "cache_stats": report.cache_stats,
        "metrics": report.metrics,
    }
    if results:
        # The first element of each root: the rest of a tree reduction's
        # array is payload ballast, never written.
        out["results"] = {k: np.asarray(v).ravel()[0]
                          for k, v in report.results.items()}
    return out


def _orch(report) -> dict:
    """An OrchestratorReport, all of it simulated."""
    return {k: getattr(report, k) for k in (
        "mode", "jobs", "completed", "failed", "makespan_s", "p50_s",
        "p95_s", "p99_s", "mean_latency_s", "mean_queue_wait_s",
        "warm_share", "cold_starts", "warm_reuses", "throttle_events",
        "peak_concurrency", "billed_usd_total", "per_tenant", "job_records",
        "per_tier", "crashes", "recovered_jobs", "tasks_resumed", "cache")}


def _pin(head: dict, whole) -> dict:
    """The pinned form of a case: its headline numbers and the digest of
    its whole projection."""
    return {**_canon(head), "digest": _digest(whole)}


def _job_pin(report, results=True) -> dict:
    proj = _job(report, results)
    return _pin({"charged_ms": report.charged_ms, "wall_s": report.wall_s,
                 "tasks": report.tasks}, proj)


def _orch_pin(report) -> dict:
    return _pin({"makespan_s": report.makespan_s,
                 "billed_usd_total": report.billed_usd_total,
                 "completed": report.completed, "jobs": report.jobs},
                _orch(report))


def _fires(bus) -> list:
    return [(r["fire_key"], r["source"], r["job_id"])
            for r in bus.fired_records()]


# ---------------------------------------------------------------------------
# Workloads
# ---------------------------------------------------------------------------


def _tree_root(report, n):
    (_, root), = report.results.items()
    assert root[0] == tree_reduction_expected(n)


def fig07_jitter_faults():
    """Latency jitter, cold starts, fault injection with retry backoff."""
    cfg = EngineConfig(
        cost=CostModel(invoke_sigma=0.3, warm_fraction=0.7, latency_seed=7),
        faults=FaultConfig(task_failure_prob=0.04, max_retries=2, seed=21,
                           retry_backoff_base_ms=1000.0))
    rep = WukongEngine(cfg).compute(
        tree_reduction_dag(64, compute_ms=250.0, payload_bytes=1 << 16))
    _tree_root(rep, 64)
    return _job_pin(rep)


def fig14_platform():
    """Warm pool, throttle and billing meter of the stateful platform."""
    cfg = EngineConfig(cost=CostModel(cold_start_ms=250.0),
                       platform=PlatformConfig(keep_alive_s=600.0),
                       num_initial_invokers=4, num_proxy_invokers=4)
    rep = WukongEngine(cfg).compute(tree_reduction_dag(64, compute_ms=25.0))
    assert rep.platform_stats["billed_usd"] > 0
    return _job_pin(rep)


def orchestrator_8_jobs():
    """Eight concurrent jobs on one shared clock, store and platform."""
    cfg = OrchestratorConfig(
        engine=EngineConfig(num_initial_invokers=4, num_proxy_invokers=4),
        workload=WorkloadConfig(
            n_jobs=8, arrival_rate_per_s=4.0, seed=0,
            tenants=(TenantSpec("t-a", 1792), TenantSpec("t-b", 896)),
            app_mix=(("tree_reduction", 1.0),), compute_ms=10.0),
        max_concurrent_jobs=4)
    rep = JobOrchestrator(cfg).run()
    assert rep.completed == 8 and rep.failed == 0
    return _orch_pin(rep)


def tree16():
    """The 16-leaf tree reduction once cross-checked against real time."""
    rep = WukongEngine().compute(tree_reduction_dag(16, compute_ms=20.0))
    _tree_root(rep, 16)
    return _job_pin(rep)


def platform_billing():
    """Billed requests, duration, GB-s and USD of a cold-start platform."""
    cfg = EngineConfig(cost=CostModel(cold_start_ms=250.0),
                       platform=PlatformConfig(),
                       num_initial_invokers=4, num_proxy_invokers=4)
    rep = WukongEngine(cfg).compute(tree_reduction_dag(16, compute_ms=2.0))
    return _job_pin(rep)


def _cached_cfg(cache, **kw):
    return EngineConfig(cost=CostModel(cold_start_ms=250.0),
                        platform=PlatformConfig(keep_alive_s=600.0,
                                                cache=cache),
                        num_initial_invokers=4, num_proxy_invokers=4, **kw)


def cached_run():
    """A spilling container cache: 16 KiB tier 0 under 64 KiB payloads."""
    rep = WukongEngine(_cached_cfg(
        CacheConfig(memory_bytes=1 << 14, disk_bytes=512 << 20))).compute(
        tree_reduction_dag(64, payload_bytes=1 << 16, compute_ms=5.0))
    _tree_root(rep, 64)
    return _job_pin(rep)


def cache_tiny_retry():
    """Injected failures and retries against a 4 KiB tier 0."""
    cfg = _cached_cfg(
        CacheConfig(memory_bytes=1 << 12, disk_bytes=512 << 20),
        faults=FaultConfig(task_failure_prob=0.08, max_retries=2, seed=11,
                           retry_backoff_base_ms=100.0))
    rep = WukongEngine(cfg).compute(
        tree_reduction_dag(64, payload_bytes=1 << 14, compute_ms=2.0))
    _tree_root(rep, 64)
    assert rep.fault_stats["task_retries"] > 0
    return _job_pin(rep)


def _ship_free():
    return WukongEngine(EngineConfig(
        cost=CostModel(schedule_ship_mbps=float("inf")),
        num_initial_invokers=4, num_proxy_invokers=4, max_concurrency=512))


def _countdown_dag(rounds):
    def step(v):
        v = np.asarray(v, dtype=float) - 1.0
        if v[0] <= 0.0:
            return v
        return Expansion(value=v,
                         tasks=(Task("next", step, (TaskRef(EXPAND_BASE),)),),
                         final="next")

    return DynamicDAG([
        Task("seed", lambda: np.array([float(rounds)]), ()),
        Task("iter", step, (TaskRef("seed"),)),
    ], max_expansion_depth=16)


def countdown():
    """Iterate-until-converged: four chained runtime expansions."""
    rep = _ship_free().compute(_countdown_dag(4))
    assert rep.tasks == 5
    return _job_pin(rep)


def dynamic_tree():
    """Data-dependent fan-out grown at run time."""
    rep = _ship_free().compute(dynamic_tree_reduction_dag(16, compute_ms=3.0))
    assert rep.results["reduce"][0] == dynamic_tree_reduction_expected(16)
    return _job_pin(rep)


def static_tree():
    """The pre-expanded equivalent of ``dynamic_tree``."""
    rep = _ship_free().compute(
        static_tree_reduction_equivalent(16, compute_ms=3.0))
    assert rep.results["reduce"][0] == dynamic_tree_reduction_expected(16)
    return _job_pin(rep)


def _gemm_checked(rep, n, bs):
    """Each output block of ``rep`` against NumPy's product of the same
    seeded inputs."""
    b = n // bs
    a, bb = (np.block([[np.asarray(_block(seed, i, j, bs), np.float64)
                        for j in range(b)] for i in range(b)])
             for seed in (1, 2))
    c = a @ bb
    for i in range(b):
        for j in range(b):
            got = np.asarray(rep.results[f"gemm-C-{i}-{j}"])
            np.testing.assert_allclose(
                got, c[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs],
                rtol=1e-4, atol=1e-4)


def gemm_compiled():
    """GEMM through the DAG compiler, as on the chip: bare jitted tasks."""
    n, bs = 256, 128
    rep = WukongEngine(EngineConfig(optimize=OptimizeConfig())).compute(
        gemm_dag(n, bs, seed_a=1, seed_b=2))
    _gemm_checked(rep, n, bs)
    return _job_pin(rep, results=False)


def gemm_flop_costed():
    """GEMM whose tasks charge simulated compute by their FLOPs."""
    n, bs = 256, 64
    rep = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(
        gemm_dag(n, bs, seed_a=1, seed_b=2, ms_per_flop=5e-5))
    _gemm_checked(rep, n, bs)
    return _job_pin(rep, results=False)


def _recovery_cfg(crash_point=None):
    return OrchestratorConfig(
        engine=EngineConfig(num_initial_invokers=2, num_proxy_invokers=2,
                            max_concurrency=64),
        workload=WorkloadConfig(
            n_jobs=6, arrival_rate_per_s=8.0, seed=3,
            tenants=(TenantSpec("t-a", 1792, tier="standard", priority=1,
                                slo_s=120.0),
                     TenantSpec("t-b", 896, tier="batch", priority=0)),
            app_mix=(("tree_reduction", 1.0),), compute_ms=5.0),
        max_concurrent_jobs=3,
        faults=FaultConfig(orchestrator_crash_point=crash_point,
                           orchestrator_crash_at=2))


def _recovered(point):
    rep = JobOrchestrator(_recovery_cfg(point)).run_with_recovery()
    assert rep.crashes == 1 and rep.completed == rep.jobs
    return _orch_pin(rep)


def recovery_admit():
    return _recovered("admit")


def recovery_dispatch():
    return _recovered("dispatch")


def recovery_complete():
    return _recovered("complete")


def recovery_journaled_untouched():
    """The shared meter's bill of each job returned from the journal."""
    orch = JobOrchestrator(_recovery_cfg("complete"))
    rep = orch.run_with_recovery()
    meter = orch.last_substrate.platform.meter
    billed = {r["job_id"]: meter.job_snapshot(f"job{r['job_id']}")["billed_usd"]
              for r in rep.job_records if r.get("from_journal")}
    assert billed
    return _pin({"journaled": len(billed)}, billed)


def shared_substrate_5_jobs():
    """Five jobs in turn on one substrate whose clock never restarts."""
    cfg = EngineConfig(num_initial_invokers=4, num_proxy_invokers=4)
    substrate = Substrate(cfg, None)
    dag = tree_reduction_dag(16, compute_ms=5.0)
    reports = []
    with substrate.clock.actor():
        for i in range(5):
            sub = substrate.job_substrate(f"job{i}", "tenant-x")
            reports.append(WukongEngine(cfg).compute(dag, substrate=sub))
    return _pin({"now_ms": substrate.clock.now_ms(),
                 "charged_ms": substrate.clock.charged_ms},
                [_job(r) for r in reports])


_ACTION = {"app": "tree_reduction", "size": 8, "tenant": "tenant-a"}


def streaming():
    """Stream arrivals and all four trigger sources, 40 events."""
    stream = StreamConfig(n_events=40, rate_per_s=40.0, seed=3,
                          flush_event="eos")
    cfg = OrchestratorConfig(
        engine=EngineConfig(num_initial_invokers=4, num_proxy_invokers=4,
                            max_concurrency=512),
        workload=WorkloadConfig(n_jobs=2, tenants=(
            TenantSpec("tenant-a"), TenantSpec("tenant-b")), seed=1),
        max_concurrent_jobs=8,
        triggers=(
            TriggerRule("window", "kv_write", _ACTION,
                        key_prefix=stream.store_prefix, window_ms=250.0),
            TriggerRule("tick", "timer",
                        {"app": "tree_reduction", "size": 8,
                         "tenant": "tenant-b"},
                        period_ms=700.0, max_fires=2),
            TriggerRule("ckpt", "job_completed",
                        {"app": "dynamic_tree", "size": 8,
                         "tenant": "tenant-b"},
                        job_app="tree_reduction", every_n=4),
            TriggerRule("flush", "external", _ACTION, event="eos",
                        flush_windows=True),
        ),
        stream=stream)
    orch = JobOrchestrator(cfg)
    rep = orch.run()
    bus = orch.last_substrate.trigger_bus
    return _pin({"makespan_s": rep.makespan_s,
                 "billed_usd_total": rep.billed_usd_total,
                 "fires": len(_fires(bus))},
                {"report": _orch(rep), "fires": _fires(bus),
                 "stream": bus.report(n_events=40).__dict__})


def traced_actor():
    """The effect journal of one actor whose charges a seed shuffles."""
    clock = EventClock()
    clock.tracer = Tracer()

    def actor():
        charges = [1.0, 2.0, 3.0, 4.0]
        random.Random(7).shuffle(charges)
        for ms in charges:
            yield ("charge", ms)
        yield ("flush",)
        return sum(charges)

    assert clock.run(actor()) == 10.0
    return _pin({"events": len(clock.tracer)}, _journal(clock.tracer))


def _journal(tracer):
    # The source file of each effect, not its line: lines move with edits.
    return [(e.seq, e.actor, e.effect, e.charge, e.src.split(":")[0])
            for e in tracer.events]


def traced_job():
    """The effect journal of a 16-leaf tree reduction, frame by frame."""
    cfg = EngineConfig(num_initial_invokers=4, num_proxy_invokers=4)
    substrate = Substrate(cfg, None)
    substrate.clock.tracer = Tracer()
    with substrate.clock.actor():
        rep = WukongEngine(cfg).compute(
            tree_reduction_dag(16, compute_ms=5.0),
            substrate=substrate.job_substrate("job0", "tenant-x"))
    _tree_root(rep, 16)
    journal = _journal(substrate.clock.tracer)
    return _pin({"events": len(journal)}, journal)


# -- the figure scripts' smoke arms ---------------------------------------


def _tree_actors(clock, leaves, compute_ms):
    """fig16's substrate tree: one frame per leaf and per inner node,
    reducing pairwise through clock queues."""
    qs = []
    for i in range(leaves):
        q = clock.queue()

        def leaf(q=q):
            yield ("charge", compute_ms)
            q.put(1)

        clock.spawn(leaf, name=f"leaf{i}")
        qs.append(q)
    while len(qs) > 1:
        nxt = []
        for i in range(0, len(qs), 2):
            a_q, b_q, out = qs[i], qs[i + 1], clock.queue()

            def node(a_q=a_q, b_q=b_q, out=out):
                a = yield ("get", a_q, None)
                b = yield ("get", b_q, None)
                yield ("charge", compute_ms)
                out.put(a + b)

            clock.spawn(node, name="node")
            nxt.append(out)
        qs = nxt

    def root(q=qs[0]):
        return (yield ("get", q, None))

    return root()


def fig16_substrate_tree():
    """fig16's 1,024-leaf substrate tier, no engine around it."""
    clock = EventClock()
    assert clock.run(_tree_actors(clock, 1024, 1.0)) == 1024
    return _pin({"now_ms": clock.now_ms(), "charged_ms": clock.charged_ms,
                 "switches": clock.switches}, [])


def fig16_engine_tier():
    """fig16's engine curve at 2,047 tasks, metrics off."""
    n = 2048
    rep = WukongEngine(EngineConfig(
        max_concurrency=4096, job_timeout_s=1e6,
        record_metrics=False)).compute(tree_reduction_dag(n, compute_ms=1.0))
    _tree_root(rep, n)
    return _job_pin(rep)


_FIG17_TENANTS = (
    TenantSpec("prem-00", 3584, tier="premium", priority=2, slo_s=30.0),
    TenantSpec("std-00", 1792, tier="standard", priority=1, slo_s=120.0),
    TenantSpec("std-01", 896, tier="standard", priority=1, slo_s=120.0),
    TenantSpec("batch-00", 1792, tier="batch", priority=0),
)


def _fig17_cfg(crash_point=None):
    return OrchestratorConfig(
        engine=EngineConfig(cost=CostModel(cold_start_ms=250.0),
                            num_initial_invokers=4, num_proxy_invokers=4,
                            max_concurrency=512),
        workload=WorkloadConfig(n_jobs=12, arrival_rate_per_s=8.0,
                                tenants=_FIG17_TENANTS, seed=0),
        max_concurrent_jobs=4,
        faults=FaultConfig(orchestrator_crash_point=crash_point,
                           orchestrator_crash_at=2))


def fig17_baseline():
    """fig17's smoke baseline: 12 jobs of the default app mix."""
    rep = JobOrchestrator(_fig17_cfg()).run()
    assert rep.completed == rep.jobs
    return _orch_pin(rep)


def _fig17_crashed(point):
    rep = JobOrchestrator(_fig17_cfg(point)).run_with_recovery()
    assert rep.crashes == 1 and rep.completed == rep.jobs
    return _orch_pin(rep)


def fig17_admit():
    return _fig17_crashed("admit")


def fig17_dispatch():
    return _fig17_crashed("dispatch")


def fig17_complete():
    return _fig17_crashed("complete")


_FIG18_ARMS = (None, CacheConfig(disk_bytes=0), CacheConfig())


def _fig18_engine(cache, optimize, invokers):
    return WukongEngine(EngineConfig(
        cost=CostModel(kv_bandwidth_mbps=5.0, stripe_threshold_bytes=8 << 10,
                       cold_start_ms=250.0),
        optimize=optimize, batch_kv_round_trips=True,
        num_initial_invokers=invokers, num_proxy_invokers=invokers,
        platform=PlatformConfig(keep_alive_s=600.0, cache=cache)))


def fig18_gemm():
    """fig18's GEMM shape in its three cache arms."""
    n, bs = 512, 128
    dag = gemm_dag(n, bs)
    reps = [_fig18_engine(c, ALL_PASSES, 8).compute(dag) for c in _FIG18_ARMS]
    return _pin({"charged_ms": [r.charged_ms for r in reps]},
                [_job(r, results=False) for r in reps])


def fig18_tree():
    """fig18's 1 MB-payload tree in its three cache arms."""
    dag = tree_reduction_dag(256, payload_bytes=1 << 20, compute_ms=5.0)
    reps = [_fig18_engine(c, NO_PASSES, 4).compute(dag) for c in _FIG18_ARMS]
    for rep in reps:
        _tree_root(rep, 256)
    return _pin({"charged_ms": [r.charged_ms for r in reps]},
                [_job(r) for r in reps])


def _fig19_cfg(n_events, crash_at=None):
    stream = StreamConfig(n_events=n_events, rate_per_s=40.0, seed=3,
                          flush_event="eos")
    faults = FaultConfig()
    if crash_at is not None:
        faults = FaultConfig(orchestrator_crash_point="dispatch",
                             orchestrator_crash_at=crash_at)
    return OrchestratorConfig(
        engine=EngineConfig(num_initial_invokers=4, num_proxy_invokers=4,
                            max_concurrency=512),
        workload=WorkloadConfig(n_jobs=2, tenants=(
            TenantSpec("tenant-a"), TenantSpec("tenant-b")), seed=1),
        max_concurrent_jobs=8,
        triggers=(
            TriggerRule("window", "kv_write",
                        {"app": "tree_reduction", "size": 8,
                         "tenant": "tenant-a"},
                        key_prefix=stream.store_prefix, window_ms=125.0),
            TriggerRule("tick", "timer",
                        {"app": "tree_reduction", "size": 8,
                         "tenant": "tenant-b"},
                        period_ms=2500.0, max_fires=2),
            TriggerRule("ckpt", "job_completed",
                        {"app": "dynamic_tree", "size": 8,
                         "tenant": "tenant-b"},
                        job_app="tree_reduction", every_n=8),
            TriggerRule("flush", "external",
                        {"app": "tree_reduction", "size": 8,
                         "tenant": "tenant-a"},
                        event="eos", flush_windows=True),
        ),
        stream=stream, faults=faults)


def _fig19(n_events, crash_at=None):
    orch = JobOrchestrator(_fig19_cfg(n_events, crash_at))
    rep = (orch.run() if crash_at is None else orch.run_with_recovery())
    assert rep.completed == rep.jobs and rep.failed == 0
    bus = orch.last_substrate.trigger_bus
    return _pin({"makespan_s": rep.makespan_s,
                 "billed_usd_total": rep.billed_usd_total,
                 "jobs": rep.jobs},
                {"report": _orch(rep), "fires": _fires(bus),
                 "stream": bus.report(n_events=n_events).__dict__})


def fig19_stream():
    """fig19's streaming arm: four trigger sources over 400 events."""
    return _fig19(400)


def fig19_crash():
    """fig19's crash arm: the dispatcher dies at its 12th dispatch."""
    return _fig19(400, crash_at=12)


def fig19_parity():
    """fig19's dynamic-vs-static arm at 16 leaves, ship-free."""
    reps = []
    for dag_fn in (dynamic_tree_reduction_dag,
                   static_tree_reduction_equivalent):
        reps.append(WukongEngine(EngineConfig(
            cost=CostModel(schedule_ship_mbps=float("inf")),
            num_initial_invokers=4, num_proxy_invokers=4,
            max_concurrency=512)).compute(dag_fn(16, compute_ms=5.0)))
    assert reps[0].charged_ms == reps[1].charged_ms
    return _pin({"charged_ms": reps[0].charged_ms},
                [_job(r) for r in reps])


WORKLOADS = {f.__name__: f for f in (
    fig07_jitter_faults, fig14_platform, orchestrator_8_jobs, tree16,
    platform_billing, cached_run, cache_tiny_retry, countdown,
    dynamic_tree, static_tree, gemm_compiled, gemm_flop_costed,
    recovery_admit, recovery_dispatch, recovery_complete,
    recovery_journaled_untouched, shared_substrate_5_jobs, streaming,
    traced_actor, traced_job,
    fig16_substrate_tree, fig16_engine_tier, fig17_baseline, fig17_admit,
    fig17_dispatch, fig17_complete, fig18_gemm, fig18_tree, fig19_stream,
    fig19_crash, fig19_parity)}


def run_pinned(name, monkeypatch):
    """Run workload ``name``, and add to its pin the frame steps of
    every clock it ran (``EventClock.run`` is the one way in)."""
    clocks = {}
    real_run = EventClock.run

    def run(self, gen):
        clocks.setdefault(id(self), self)
        return real_run(self, gen)

    monkeypatch.setattr(EventClock, "run", run)
    pin = WORKLOADS[name]()
    pin["frame_steps"] = sum(c.switches for c in clocks.values())
    return pin


# Recorded from the tree before the thread and real-time clocks went.
PINS = {
    "fig07_jitter_faults": {
        "charged_ms": 23715.6296932,
        "wall_s": 3.60246400022,
        "tasks": 63,
        "digest": "a312eb52fe019b0e",
        "frame_steps": 517,
    },
    "fig14_platform": {
        "charged_ms": 5004.11033667,
        "wall_s": 1.05860242333,
        "tasks": 63,
        "digest": "6c783c0d7f14d18c",
        "frame_steps": 418,
    },
    "orchestrator_8_jobs": {
        "makespan_s": 2.07298354857,
        "billed_usd_total": 3.78188064375e-05,
        "completed": 8,
        "jobs": 8,
        "digest": "f5fe9107ecc4126c",
        "frame_steps": 865,
    },
    "tree16": {
        "charged_ms": 719.101893333,
        "wall_s": 0.135600331667,
        "tasks": 15,
        "digest": "dc190e3f5801cc6d",
        "frame_steps": 190,
    },
    "platform_billing": {
        "charged_ms": 1449.10189333,
        "wall_s": 0.363600516667,
        "tasks": 15,
        "digest": "e590ee18a7eb6123",
        "frame_steps": 125,
    },
    "cached_run": {
        "charged_ms": 3075.13519,
        "wall_s": 0.698150708333,
        "tasks": 63,
        "digest": "372a1c0316eab103",
        "frame_steps": 509,
    },
    "cache_tiny_retry": {
        "charged_ms": 4034.34244333,
        "wall_s": 1.22672499167,
        "tasks": 63,
        "digest": "66be59e8510d7db6",
        "frame_steps": 523,
    },
    "countdown": {
        "charged_ms": 51.1000266667,
        "wall_s": 0.0511000266667,
        "tasks": 5,
        "digest": "0f0e624b3769f90d",
        "frame_steps": 30,
    },
    "dynamic_tree": {
        "charged_ms": 529.602333333,
        "wall_s": 0.176101173333,
        "tasks": 18,
        "digest": "2f4b90a1d6b97750",
        "frame_steps": 157,
    },
    "static_tree": {
        "charged_ms": 529.602333333,
        "wall_s": 0.176101173333,
        "tasks": 18,
        "digest": "75246124c33d8aa2",
        "frame_steps": 157,
    },
    "gemm_compiled": {
        "charged_ms": 625.53068,
        "wall_s": 0.108020593333,
        "tasks": 12,
        "digest": "e706094ed71a51c6",
        "frame_steps": 202,
    },
    "gemm_flop_costed": {
        "charged_ms": 5123.65730667,
        "wall_s": 0.300976546667,
        "tasks": 144,
        "digest": "b39379ff4147c3c7",
        "frame_steps": 1153,
    },
    "recovery_admit": {
        "makespan_s": 0.877803235,
        "billed_usd_total": 1.27770992875e-05,
        "completed": 6,
        "jobs": 6,
        "digest": "ba7f25c547505f36",
        "frame_steps": 465,
    },
    "recovery_dispatch": {
        "makespan_s": 0.877803235,
        "billed_usd_total": 1.53562695125e-05,
        "completed": 6,
        "jobs": 6,
        "digest": "eb37666edca7bbd8",
        "frame_steps": 531,
    },
    "recovery_complete": {
        "makespan_s": 0.878307318333,
        "billed_usd_total": 1.40146001625e-05,
        "completed": 6,
        "jobs": 6,
        "digest": "7dcf94489bacb32e",
        "frame_steps": 530,
    },
    "recovery_journaled_untouched": {
        "journaled": 2,
        "digest": "24588d60f28bde28",
        "frame_steps": 530,
    },
    "shared_substrate_5_jobs": {
        "now_ms": 630.502583333,
        "charged_ms": 2470.50946667,
        "digest": "1ea1f4fdad3a3733",
        "frame_steps": 605,
    },
    "streaming": {
        "makespan_s": 1.48353091231,
        "billed_usd_total": 9.2037652075e-05,
        "fires": 12,
        "digest": "6f0d82599d5ec0f5",
        "frame_steps": 1674,
    },
    "traced_actor": {"events": 5, "digest": "e8fbea96c6706d6e", "frame_steps": 5},
    "traced_job": {"events": 139, "digest": "03b20a2924cab468", "frame_steps": 121},
    "fig16_substrate_tree": {
        "now_ms": 11.0,
        "charged_ms": 2047.0,
        "switches": 6142,
        "digest": "4f53cda18c2baa0c",
        "frame_steps": 6142,
    },
    "fig16_engine_tier": {
        "charged_ms": 55806.6569033,
        "wall_s": 2.62712651167,
        "tasks": 2047,
        "digest": "7c4e3ede841c8668",
        "frame_steps": 13117,
    },
    "fig17_baseline": {
        "makespan_s": 2.84826837765,
        "billed_usd_total": 0.000175129420125,
        "completed": 12,
        "jobs": 12,
        "digest": "2afd150ee9b1314a",
        "frame_steps": 3030,
    },
    "fig17_admit": {
        "makespan_s": 2.84826837765,
        "billed_usd_total": 0.00017527525375,
        "completed": 12,
        "jobs": 12,
        "digest": "fa2b547cb1f6d657",
        "frame_steps": 3084,
    },
    "fig17_dispatch": {
        "makespan_s": 2.84826837765,
        "billed_usd_total": 0.000195604452675,
        "completed": 12,
        "jobs": 12,
        "digest": "4fd437a77e6efd05",
        "frame_steps": 3344,
    },
    "fig17_complete": {
        "makespan_s": 2.84826837765,
        "billed_usd_total": 0.000196337785075,
        "completed": 12,
        "jobs": 12,
        "digest": "e0946cd4e2120d36",
        "frame_steps": 3476,
    },
    "fig18_gemm": {
        "charged_ms": [4446.894, 4332.09, 4332.09],
        "digest": "53d47aaab65114d7",
        "frame_steps": 1833,
    },
    "fig18_tree": {
        "charged_ms": [21284.33917, 21070.62237, 21070.62237],
        "digest": "1093a6181d8c57e9",
        "frame_steps": 5138,
    },
    "fig19_stream": {
        "makespan_s": 10.24948068,
        "billed_usd_total": 0.000548313426625,
        "jobs": 98,
        "digest": "f979e288d38119e5",
        "frame_steps": 10298,
    },
    "fig19_crash": {
        "makespan_s": 10.24948068,
        "billed_usd_total": 0.000562688452175,
        "jobs": 98,
        "digest": "6359c166bb5069a1",
        "frame_steps": 12071,
    },
    "fig19_parity": {"charged_ms": 565.602333333, "digest": "3ae4be31167ac1b3", "frame_steps": 314},
}


@pytest.mark.parametrize("name", list(WORKLOADS))
def test_pinned(name, monkeypatch):
    assert run_pinned(name, monkeypatch) == PINS[name]
