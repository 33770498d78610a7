"""Fig. 19 (beyond-paper): steady-state streaming via the trigger bus.

The trigger subsystem (repro.core.triggers) claims that event-fired
jobs are a first-class, durable, deterministic workload: a Poisson
event stream lands in the KV store, tumbling windows close and fire
tree-reduction jobs through the PR 5 orchestrator, timer / job-
completion / external triggers fire alongside, and the whole run is
bit-identical across repeats. Dynamic DAGs (runtime graph expansion) claim charged-cost
parity with their static equivalents, and a dispatcher crash mid-
stream claims exactly-once window fires via the fire journal.

Fig. 19 prices those claims with three arms:

- **streaming**: ``StreamConfig`` arrivals + four trigger rules (one
  per source type), run twice; gates on >= 64 window-close jobs, >= 1
  fire per source, zero failures, and bit-identical steady-state
  metrics (sustained jobs/s, event-to-result p50/p95/p99, backlog,
  window fire-key set) across the runs;
- **parity**: ``dynamic_tree_reduction_dag`` vs its pre-expanded
  static equivalent on a ship-free engine — results and charged_ms
  must match bit for bit;
- **crash**: the streaming config crashed at the "dispatch" protocol
  point and recovered via ``run_with_recovery`` — the recovered run
  must complete every job with the same window fire-key set as the
  uncrashed baseline and no duplicated trigger job id (the journal
  dedupe is what makes re-delivered events exactly-once).
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmarks import common
from repro.apps import (
    dynamic_tree_reduction_dag,
    dynamic_tree_reduction_expected,
    static_tree_reduction_equivalent,
)
from repro.core import (
    CostModel,
    EngineConfig,
    FaultConfig,
    JobOrchestrator,
    OrchestratorConfig,
    StreamConfig,
    TenantSpec,
    TriggerRule,
    WorkloadConfig,
    WukongEngine,
)

_TENANTS = (TenantSpec("tenant-a"), TenantSpec("tenant-b"))

# Metrics that must be bit-identical across repeated runs (the
# determinism gate).
_DETERMINISM_KEYS = (
    "wall_s", "jobs", "completed", "failed", "fires", "windows_closed",
    "window_jobs_completed", "sustained_jobs_per_s",
    "event_to_result_p50_s", "event_to_result_p95_s",
    "event_to_result_p99_s", "mean_backlog", "max_backlog",
    "window_fire_digest", "billed_usd_total",
)


def _engine_config(**cost_kw) -> EngineConfig:
    return EngineConfig(cost=CostModel(**cost_kw),
                        num_initial_invokers=4, num_proxy_invokers=4,
                        max_concurrency=512)


def _stream(n_events: int) -> StreamConfig:
    return StreamConfig(n_events=n_events, rate_per_s=40.0, seed=3,
                        flush_event="eos")


def _rules(stream: StreamConfig, window_ms: float) -> "tuple[TriggerRule, ...]":
    # One rule per trigger source type; the acceptance gate requires
    # each of the four to fire at least one job.
    return (
        TriggerRule("window", "kv_write",
                    {"app": "tree_reduction", "size": 8,
                     "tenant": "tenant-a"},
                    key_prefix=stream.store_prefix, window_ms=window_ms),
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
    )


def _orch_config(n_events: int, window_ms: float,
                 crash_at: "int | None" = None) -> OrchestratorConfig:
    stream = _stream(n_events)
    faults = FaultConfig()
    if crash_at is not None:
        faults = FaultConfig(orchestrator_crash_point="dispatch",
                             orchestrator_crash_at=crash_at)
    return OrchestratorConfig(
        engine=_engine_config(),
        workload=WorkloadConfig(n_jobs=2, tenants=_TENANTS, seed=1),
        max_concurrent_jobs=8,
        triggers=_rules(stream, window_ms),
        stream=stream,
        faults=faults,
    )


def _row(label: str, rep, bus, n_events: int,
         derived_extra: str = "") -> dict:
    srep = bus.report(n_events=n_events)
    fired = bus.fired_records()
    window_keys = sorted(r["fire_key"] for r in fired
                         if r["source"] == "kv_write")
    digest = hashlib.sha256(
        "\n".join(window_keys).encode()).hexdigest()[:16]
    job_ids = [r["job_id"] for r in fired]
    row = {
        "label": label,
        "wall_s": rep.makespan_s,
        "jobs": rep.jobs,
        "completed": rep.completed,
        "failed": rep.failed,
        "crashes": rep.crashes,
        "fires": dict(sorted(srep.fires.items())),
        "windows_closed": srep.windows_closed,
        "window_jobs_completed": srep.window_jobs_completed,
        "sustained_jobs_per_s": srep.sustained_jobs_per_s,
        "event_to_result_p50_s": srep.event_to_result_p50_s,
        "event_to_result_p95_s": srep.event_to_result_p95_s,
        "event_to_result_p99_s": srep.event_to_result_p99_s,
        "mean_backlog": srep.mean_backlog,
        "max_backlog": srep.max_backlog,
        "duplicate_fires_suppressed": srep.duplicate_fires_suppressed,
        "window_fire_digest": digest,
        "dup_job_ids": len(job_ids) - len(set(job_ids)),
        "billed_usd_total": rep.billed_usd_total,
    }
    bits = [derived_extra] if derived_extra else []
    bits.append(f"{rep.completed}/{rep.jobs}jobs")
    bits.append(f"w={srep.windows_closed}")
    bits.append(f"rate={srep.sustained_jobs_per_s:.2f}/s")
    bits.append(f"p99={srep.event_to_result_p99_s:.3f}s")
    row["derived"] = " ".join(bits)
    return row


def _parity_row(n: int) -> dict:
    """Dynamic-vs-static charged parity on a ship-free engine.

    ``schedule_ship_mbps=inf`` removes the static-schedule shipping
    charge (the dynamic arm's expansion schedules are built after
    dispatch, so shipping is the one structural cost the two arms
    cannot share); everything else — invokes, KV traffic, counter
    registration, compute — must then price identically.
    """
    expected = dynamic_tree_reduction_expected(n)
    reports = {}
    for arm, dag_fn in (("dynamic", dynamic_tree_reduction_dag),
                        ("static", static_tree_reduction_equivalent)):
        eng = WukongEngine(
            _engine_config(schedule_ship_mbps=float("inf")))
        reports[arm] = eng.compute(dag_fn(n, compute_ms=5.0))
    dyn, sta = reports["dynamic"], reports["static"]
    correct = (np.allclose(dyn.results["reduce"], expected)
               and np.allclose(sta.results["reduce"], expected))
    parity = (dyn.charged_ms == sta.charged_ms
              and dyn.tasks == sta.tasks
              and np.array_equal(np.asarray(dyn.results["reduce"]),
                                 np.asarray(sta.results["reduce"])))
    return {
        "label": f"parity_n{n}",
        "wall_s": dyn.wall_s,
        "charged_ms": dyn.charged_ms,
        "static_charged_ms": sta.charged_ms,
        "tasks": dyn.tasks,
        "kv_stats": dyn.kv_stats,
        "parity": parity,
        "correct": correct,
        "derived": (f"dyn={dyn.charged_ms:.3f}ms "
                    f"static={sta.charged_ms:.3f}ms "
                    f"parity={'ok' if parity else 'BROKEN'}"),
    }


def run(n_events: int = 400, window_ms: float = 125.0,
        crash_ats: "tuple[int, ...]" = (12,),
        parity_n: int = 16) -> "list[dict]":
    rows: list[dict] = []
    for rep_i in range(2):
        orch = JobOrchestrator(_orch_config(n_events, window_ms))
        rep = orch.run()
        rows.append(_row(f"stream_run{rep_i + 1}", rep,
                         orch.last_substrate.trigger_bus, n_events,
                         derived_extra=f"{n_events}ev@40/s"))
    for crash_at in crash_ats:
        orch = JobOrchestrator(
            _orch_config(n_events, window_ms, crash_at=crash_at))
        rep = orch.run_with_recovery()
        rows.append(_row(f"crash_at{crash_at}", rep,
                         orch.last_substrate.trigger_bus, n_events,
                         derived_extra=f"crash@dispatch#{crash_at}"))
    rows.append(_parity_row(parity_n))
    return rows


def check_gates(rows: "list[dict]") -> None:
    """CI regression gate (run.py --smoke): deterministic steady-state
    streaming, all four trigger sources live, exactly-once fires across
    a mid-stream dispatcher crash, dynamic/static charged parity."""
    import sys

    stream_rows = [r for r in rows if r["label"].startswith("stream_run")]
    assert stream_rows, "streaming gate: no streaming rows in fig19"
    for row in stream_rows:
        assert row["completed"] == row["jobs"] and row["failed"] == 0, (
            f"streaming regression: {row['label']} completed "
            f"{row['completed']}/{row['jobs']} ({row['failed']} failed)")
        assert row["windows_closed"] >= 64, (
            f"streaming regression: {row['label']} closed only "
            f"{row['windows_closed']} windows (need >= 64)")
        for source in ("timer", "kv_write", "job_completed", "external"):
            assert row["fires"].get(source, 0) >= 1, (
                f"streaming regression: {row['label']} fired no "
                f"{source} job")
        assert row["dup_job_ids"] == 0, (
            f"streaming regression: {row['label']} allocated duplicate "
            f"trigger job ids")
    base = stream_rows[0]
    for row in stream_rows[1:]:
        for key in _DETERMINISM_KEYS:
            assert row[key] == base[key], (
                f"determinism regression: {row['label']}.{key} = "
                f"{row[key]!r} != {base['label']}.{key} = {base[key]!r}")

    crashed = [r for r in rows if r["label"].startswith("crash_at")]
    assert crashed, "crash gate: no crashed runs in fig19 rows"
    for row in crashed:
        assert row["crashes"] > 0, (
            f"crash gate: {row['label']} never actually crashed")
        assert row["completed"] == row["jobs"] and row["failed"] == 0, (
            f"crash regression: {row['label']} completed "
            f"{row['completed']}/{row['jobs']} ({row['failed']} failed)")
        assert row["window_fire_digest"] == base["window_fire_digest"], (
            f"crash regression: {row['label']} window fire-key set "
            f"diverged from the uncrashed baseline (lost or spurious "
            f"window job)")
        assert row["dup_job_ids"] == 0, (
            f"crash regression: {row['label']} duplicated a trigger "
            f"job id across recovery")

    parity = [r for r in rows if r["label"].startswith("parity_")]
    assert parity, "parity gate: no parity rows in fig19"
    for row in parity:
        assert row["correct"], (
            f"parity regression: {row['label']} computed a wrong "
            f"reduction result")
        assert row["parity"], (
            f"parity regression: {row['label']} dynamic charged "
            f"{row['charged_ms']} != static {row['static_charged_ms']}")

    print(f"# streaming gate OK: {len(stream_rows)} runs bit-identical "
          f"({base['windows_closed']} windows, "
          f"{base['sustained_jobs_per_s']:.2f} jobs/s sustained), "
          f"{len(crashed)} crashed sweeps exactly-once, "
          "dynamic/static parity",
          file=sys.stderr)


def main() -> None:
    common.emit(run(), "fig19")


if __name__ == "__main__":
    main()
