"""Fig. 17 (beyond-paper): orchestrator crash recovery cost curves.

The durable control plane (repro.core.statemachine + the orchestrator's
journal-then-act dispatch loop) claims that killing the dispatcher at
any protocol point loses no completed work: a fresh orchestrator replays
the journal, returns journaled-complete jobs verbatim (bit-identical
billing — no double execution), re-admits in-flight jobs with resume
semantics over their durable task outputs, and purges orphaned
namespaces.

Fig. 17 prices that claim. For each crash point ("admit" — after
journaling ADMITTED, before the runner exists; "dispatch" — after the
runner actor is spawned; "complete" — after journaling the terminal
record, before the namespace purge) x crash occurrence, a run is
crashed once via ``run_with_recovery`` and compared against the
uncrashed baseline on:

- **recovery overhead**: makespan delta vs the baseline (replay +
  re-admission + redone work);
- **re-executed work**: extra task attempts beyond the baseline's
  (work the crash forced the system to redo despite resume);
- **resumed work**: task outputs reused from the durable store instead
  of re-executed;
- **journal parity**: every journaled-complete job's billed USD and
  latency bit-identical to the baseline record — the no-double-billing
  acceptance criterion, asserted by the ``--smoke`` gate.
"""
from __future__ import annotations

from benchmarks import common
from repro.core import (
    CostModel,
    EngineConfig,
    FaultConfig,
    JobOrchestrator,
    OrchestratorConfig,
    TenantSpec,
    WorkloadConfig,
)

CRASH_POINTS = ("admit", "dispatch", "complete")

# Tiered tenants: the recovery sweep doubles as the per-tier SLO
# accounting demo (premium admitted first, batch last, both recovered).
_TENANTS = (
    TenantSpec("prem-00", 3584, tier="premium", priority=2, slo_s=30.0),
    TenantSpec("std-00", 1792, tier="standard", priority=1, slo_s=120.0),
    TenantSpec("std-01", 896, tier="standard", priority=1, slo_s=120.0),
    TenantSpec("batch-00", 1792, tier="batch", priority=0),
)


def _engine_config() -> EngineConfig:
    return EngineConfig(cost=CostModel(cold_start_ms=250.0),
                        num_initial_invokers=4, num_proxy_invokers=4,
                        max_concurrency=512)


def _orch_config(n_jobs: int, rate: float,
                 crash_point: "str | None" = None, crash_at: int = 1,
                 max_concurrent_jobs: int = 8, seed: int = 0,
                 ) -> OrchestratorConfig:
    return OrchestratorConfig(
        engine=_engine_config(),
        workload=WorkloadConfig(n_jobs=n_jobs, arrival_rate_per_s=rate,
                                tenants=_TENANTS, seed=seed),
        max_concurrent_jobs=max_concurrent_jobs,
        faults=FaultConfig(orchestrator_crash_point=crash_point,
                           orchestrator_crash_at=crash_at),
    )


def _total_attempts(rep) -> int:
    return sum(r.get("fault_stats", {}).get("task_attempts", 0)
               for r in rep.job_records)


def _journal_parity(rep, base_by_id: dict) -> "tuple[bool, bool]":
    """(record parity, per-tenant billing-sum parity) of the recovered
    run's journaled-complete jobs vs the uncrashed baseline."""
    from_journal = [r for r in rep.job_records if r.get("from_journal")]
    rec_ok = all(
        r["billed_usd"] == base_by_id[r["job_id"]]["billed_usd"]
        and r["latency_s"] == base_by_id[r["job_id"]]["latency_s"]
        for r in from_journal)
    tenants = {r["tenant"] for r in from_journal}
    sums_ok = all(
        sum(r["billed_usd"] for r in from_journal if r["tenant"] == t)
        == sum(base_by_id[r["job_id"]]["billed_usd"]
               for r in from_journal if r["tenant"] == t)
        for t in tenants)
    return rec_ok, sums_ok


def _row(label: str, rep, base=None, derived: str = "") -> dict:
    row = {
        "label": label,
        "wall_s": rep.makespan_s,
        "jobs": rep.jobs,
        "completed": rep.completed,
        "failed": rep.failed,
        "crashes": rep.crashes,
        "recovered_jobs": rep.recovered_jobs,
        "tasks_resumed": rep.tasks_resumed,
        "task_attempts": _total_attempts(rep),
        "p50_s": rep.p50_s,
        "p99_s": rep.p99_s,
        "billed_usd_total": rep.billed_usd_total,
        "per_tier": rep.per_tier,
    }
    bits = [derived] if derived else []
    if base is not None:
        base_by_id = {r["job_id"]: r for r in base.job_records}
        rec_ok, sums_ok = _journal_parity(rep, base_by_id)
        n_journal = sum(1 for r in rep.job_records if r.get("from_journal"))
        row["from_journal"] = n_journal
        row["journal_parity"] = rec_ok
        row["billing_parity"] = sums_ok
        row["recovery_overhead_s"] = rep.makespan_s - base.makespan_s
        row["reexecuted_attempts"] = (_total_attempts(rep)
                                      - _total_attempts(base))
        bits.append(f"overhead={row['recovery_overhead_s']:.3f}s")
        bits.append(f"redo={row['reexecuted_attempts']}attempts")
        bits.append(f"resumed={rep.tasks_resumed}")
        bits.append(f"parity={'ok' if rec_ok and sums_ok else 'BROKEN'}")
    else:
        bits.append(f"{rep.jobs}jobs")
        bits.append(f"p50={rep.p50_s:.3f}s")
    row["derived"] = " ".join(bits)
    return row


def run(n_jobs: int = 24, rate: float = 8.0,
        crash_ats: "tuple[int, ...]" = (1, 4),
        max_concurrent_jobs: int = 8) -> "list[dict]":
    rows: list[dict] = []
    base = JobOrchestrator(
        _orch_config(n_jobs, rate,
                     max_concurrent_jobs=max_concurrent_jobs)).run()
    rows.append(_row("baseline", base, derived=f"{n_jobs}jobs@r{rate:g}"))
    for point in CRASH_POINTS:
        for crash_at in crash_ats:
            cfg = _orch_config(n_jobs, rate, crash_point=point,
                               crash_at=crash_at,
                               max_concurrent_jobs=max_concurrent_jobs)
            rep = JobOrchestrator(cfg).run_with_recovery()
            rows.append(_row(f"{point}_at{crash_at}", rep, base=base))
    return rows


def check_gates(rows: "list[dict]") -> None:
    """CI regression gate (run.py --smoke): every crashed run recovered
    completely with bit-identical journal billing."""
    crashed = [r for r in rows if "crashes" in r and r["crashes"] > 0]
    assert crashed, "recovery gate: no crashed runs in fig17 rows"
    for row in crashed:
        assert row["completed"] == row["jobs"], (
            f"recovery regression: {row['label']} completed "
            f"{row['completed']}/{row['jobs']} jobs after recovery")
        assert row["failed"] == 0, (
            f"recovery regression: {row['label']} failed {row['failed']}")
        assert row["journal_parity"], (
            f"recovery regression: {row['label']} returned journaled "
            f"records differing from the uncrashed baseline")
        assert row["billing_parity"], (
            f"recovery regression: {row['label']} per-tenant billing of "
            f"journaled-complete jobs diverged from the baseline")
    # at least one sweep point must exercise actual resume-over-durable-
    # outputs (otherwise the resume path is silently untested)
    assert any(r["tasks_resumed"] > 0 for r in crashed), (
        "recovery regression: no sweep point resumed durable outputs")
    import sys
    resumed = sum(r["tasks_resumed"] for r in crashed)
    print(f"# recovery gate OK: {len(crashed)} crashed sweeps recovered "
          f"to completion, journal billing bit-identical, "
          f"{resumed} task outputs resumed", file=sys.stderr)


def main() -> None:
    common.emit(run(), "fig17")


if __name__ == "__main__":
    main()
