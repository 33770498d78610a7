"""Fig. 15 (beyond-paper): multi-tenant traffic on ONE shared platform.

The paper benchmarks one job at a time; its premise (fine-grained tasks
on a shared auto-scaling provider) only pays off under *traffic* — many
jobs from many tenants contending for one account's warm-container pool
and concurrency cap (the ServerMix / Triggerflow regime). Fig. 15 runs
the ``JobOrchestrator`` (repro.core.orchestrator) over a seeded Poisson
workload with a heavy-tailed mix of the paper's four applications and
sweeps:

1. **arrival rate** — shared-account vs isolated-per-job platforms at
   each rate: the shared pool converts later jobs' cold starts into
   warm reuses; isolation is the one-job-at-a-time assumption PRs 1-4
   baked in, priced out.
2. **tenant count** — more tenants on one account means each tenant's
   per-function warm pool sees a thinner slice of the traffic: the
   warm-share (and with it p50) degrades — pooling has economies of
   *scale per function*, not per account.

Every row reports job-latency percentiles (p50/p95/p99 of arrival ->
completion), per-tenant billed USD, warm share, and peak account
concurrency. Deterministic under the virtual clock; ``run.py --smoke``
re-runs the smoke pair and asserts bit-identity (including per-tenant
billed USD) plus shared-p50 strictly below isolated-p50.
"""
from __future__ import annotations

import dataclasses

from benchmarks import common
from repro.core import (
    CostModel,
    EngineConfig,
    JobOrchestrator,
    OrchestratorConfig,
    TenantSpec,
    WorkloadConfig,
)

# Memory ladder cycled over generated tenants: two standard functions,
# one small/slow/cheap-per-GB-s, one large/fast.
_TENANT_MEMORY_LADDER = (1792, 1792, 896, 3584)


def tenants_for(count: int) -> "tuple[TenantSpec, ...]":
    return tuple(
        TenantSpec(f"tenant-{i:02d}",
                   _TENANT_MEMORY_LADDER[i % len(_TENANT_MEMORY_LADDER)])
        for i in range(count)
    )


def _engine_config() -> EngineConfig:
    # Per-job engine preset: small invoker pools (N jobs run at once on
    # one machine) on the shared benchmark cost model.
    return EngineConfig(cost=CostModel(cold_start_ms=250.0),
                        num_initial_invokers=4, num_proxy_invokers=4,
                        max_concurrency=512)


def orchestrate(n_jobs: int, rate: float, n_tenants: int,
                isolated: bool, max_concurrent_jobs: int = 32,
                seed: int = 0):
    cfg = OrchestratorConfig(
        engine=_engine_config(),
        workload=WorkloadConfig(n_jobs=n_jobs, arrival_rate_per_s=rate,
                                tenants=tenants_for(n_tenants), seed=seed),
        max_concurrent_jobs=max_concurrent_jobs,
        isolate_platform=isolated,
    )
    return JobOrchestrator(cfg).run()


def _row(label: str, rep, derived: str = "") -> dict:
    bits = [derived] if derived else []
    bits.append(f"p50={rep.p50_s:.3f}s/p95={rep.p95_s:.3f}s"
                f"/p99={rep.p99_s:.3f}s")
    bits.append(f"warm={rep.warm_share * 100:.0f}%")
    bits.append(f"billed=${rep.billed_usd_total:.6f}")
    summary = dataclasses.asdict(rep)
    summary.pop("job_records")  # per-job detail stays out of the JSON
    return {
        "label": label,
        # wall_s = simulated makespan of the whole traffic trace
        "wall_s": rep.makespan_s,
        "tasks": sum(r.get("tasks", 0) for r in rep.job_records),
        "executors": sum(r.get("executors", 0) for r in rep.job_records),
        "p50_s": rep.p50_s,
        "p95_s": rep.p95_s,
        "p99_s": rep.p99_s,
        "per_tenant_billed": {t: blk["billed_usd"]
                              for t, blk in rep.per_tenant.items()},
        "platform_stats": summary,
        "derived": " ".join(bits),
    }


def shared_isolated_pair(n_jobs: int, rate: float, n_tenants: int,
                         max_concurrent_jobs: int = 32) -> "tuple[dict, dict]":
    """The comparison the smoke gate asserts on: the SAME workload on
    one shared account vs per-job private platforms. The only difference
    is platform sharing, so the latency gap is exactly the value of
    cross-job warm reuse (minus shared-cap contention)."""
    rows = []
    for label, isolated in (("shared_pool", False), ("isolated_per_job", True)):
        rep = orchestrate(n_jobs, rate, n_tenants, isolated,
                          max_concurrent_jobs)
        rows.append(_row(f"{label}_r{rate:g}_t{n_tenants}", rep,
                         derived=f"{n_jobs}jobs"))
    return rows[0], rows[1]


def run(n_jobs: int = 128,
        rates: "tuple[float, ...]" = (2.0, 8.0),
        tenant_counts: "tuple[int, ...]" = (2, 4, 8),
        max_concurrent_jobs: int = 32) -> "list[dict]":
    rows: list[dict] = []

    # -- 1. arrival-rate sweep: shared vs isolated at each rate -------------
    for rate in rates:
        shared, isolated = shared_isolated_pair(
            n_jobs, rate, n_tenants=4,
            max_concurrent_jobs=max_concurrent_jobs)
        rows += [shared, isolated]

    # -- 2. tenant-count sweep on the shared account ------------------------
    for n_tenants in tenant_counts:
        rep = orchestrate(n_jobs, rates[0], n_tenants, isolated=False,
                          max_concurrent_jobs=max_concurrent_jobs)
        rows.append(_row(f"shared_tenants{n_tenants}", rep,
                         derived=f"{n_jobs}jobs@r{rates[0]:g}"))
    return rows


def main() -> None:
    common.emit(run(), "fig15")


if __name__ == "__main__":
    main()
