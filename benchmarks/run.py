"""Benchmark entry point: one module per paper figure.

Prints ``name,us_per_call,derived`` CSV rows and writes a
``BENCH_results.json`` snapshot (engine -> wall_s / charged_ms /
kv_stats per figure) at the repo root so the perf trajectory is tracked
across PRs.

Benchmarks run on the deterministic simulation clock: ``wall_s`` is the
simulated makespan, bit-identical across runs. Problem-size knobs:
``--quick`` (smaller sizes) and ``--smoke`` (toy sizes; a CI regression
gate that executes every figure's engines end-to-end in seconds, plus
the data-plane, platform, multi-tenant, scale, recovery, locality and
streaming gates).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

RESULTS_JSON = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_results.json"
)


def _json_row(row: dict) -> dict:
    """The per-PR trajectory record for one engine/config series."""
    out = {
        "wall_s": row["wall_s"],
        "charged_ms": row.get("charged_ms"),
        "kv_stats": row.get("kv_stats"),
        "tasks": row.get("tasks"),
        "executors": row.get("executors"),
        # Provider-model counters (cold/warm starts, throttles, billed
        # USD in pool mode; invoker cold starts in every mode).
        "platform_stats": row.get("platform_stats"),
    }
    if row.get("cache_stats"):
        # Locality trajectory (fig18): per-tier hits/misses/evictions,
        # tier-0 hit rate, and bytes served locally instead of from the
        # shared KV store.
        out["cache_stats"] = row["cache_stats"]
        out["hit_rate"] = row.get("hit_rate")
        out["bytes_local"] = row.get("bytes_local")
    return out


def _time_schedule_generation() -> dict:
    """Host-side hot path trajectory: O(V+E) sweep vs the paper's
    per-leaf DFS on a 512-leaf tree reduction (printed + recorded in
    BENCH_results.json so regressions are visible across PRs)."""
    import gc
    import time as _t

    from repro.apps import tree_reduction_dag
    from repro.core.optimize import compile_dag
    from repro.core.schedule import (
        generate_static_schedules,
        generate_static_schedules_dfs,
    )

    dag = compile_dag(tree_reduction_dag(1024))  # 512 leaves

    # Interleave the two implementations so drifting background load
    # lands on both equally (serial best-of-N loops skew the ratio
    # whenever the machine quiets down between them).
    dfs_ts, sweep_ts = [], []
    gc.disable()
    try:
        for _ in range(20):
            t0 = _t.perf_counter()
            generate_static_schedules_dfs(dag)
            dfs_ts.append(_t.perf_counter() - t0)
            t0 = _t.perf_counter()
            generate_static_schedules(dag)
            sweep_ts.append(_t.perf_counter() - t0)
    finally:
        gc.enable()
    dfs_ms = min(dfs_ts) * 1e3
    sweep_ms = min(sweep_ts) * 1e3
    out = {"leaves": 512, "dfs_ms": dfs_ms, "sweep_ms": sweep_ms,
           "speedup": dfs_ms / sweep_ms}
    print(f"# schedule-gen (512-leaf TR): per-leaf DFS {dfs_ms:.2f}ms, "
          f"O(V+E) sweep {sweep_ms:.2f}ms, {out['speedup']:.1f}x faster",
          file=sys.stderr)
    return out


def _check_platform_gate(rows_by_fig: dict, smoke_kwargs: dict) -> None:
    """CI regression gate for the stateful platform model:

    - *determinism*: re-running the fig14 warm/cold smoke workload must
      reproduce the recorded run bit-identically — ``platform_stats``
      (including billed USD), charged ms, and simulated makespan;
    - *warm pool pays*: container reuse must strictly lower the charged
      simulated latency relative to the all-cold (keep_alive=0) pool.
    """
    from benchmarks import fig14_platform

    rows = {r["label"]: r for r in rows_by_fig.get("fig14", [])}
    warm, cold = rows.get("warm_pool"), rows.get("cold_pool")
    if warm is None or cold is None:
        return
    warm2, cold2 = fig14_platform.warm_cold_pair(
        n=smoke_kwargs["n"], compute_ms=smoke_kwargs["compute_ms"],
        lanes=smoke_kwargs["pool_lanes"])
    for first, second in ((warm, warm2), (cold, cold2)):
        for field in ("platform_stats", "charged_ms", "wall_s"):
            if first[field] != second[field]:
                raise SystemExit(
                    f"platform regression: {first['label']} not "
                    f"deterministic across runs — {field} "
                    f"{first[field]!r} != {second[field]!r}")
    if not warm["charged_ms"] < cold["charged_ms"]:
        raise SystemExit(
            f"platform regression: warm pool charged "
            f"{warm['charged_ms']:.1f}ms, not strictly below the "
            f"all-cold pool's {cold['charged_ms']:.1f}ms")
    ps = warm["platform_stats"]
    if not ps["warm_reuses"] > 0:
        raise SystemExit("platform regression: warm pool saw no reuse")
    saved = (1 - warm["charged_ms"] / cold["charged_ms"]) * 100
    print(f"# platform gate OK: deterministic billed "
          f"${ps['billed_usd']:.6f}; warm pool charged "
          f"{warm['charged_ms']:.1f}ms vs cold {cold['charged_ms']:.1f}ms "
          f"({saved:.1f}% saved, {ps['warm_reuses']} reuses)",
          file=sys.stderr)


def _check_multitenant_gate(rows_by_fig: dict, smoke_kwargs: dict) -> None:
    """CI regression gate for the multi-tenant orchestrator (fig15):

    - *scale*: the smoke workload must run >= 32 jobs from >= 4 tenants
      on one shared platform;
    - *determinism*: re-running the shared/isolated smoke pair must
      reproduce the recorded rows bit-identically — latency percentiles
      AND per-tenant billed USD;
    - *pooling pays*: the shared warm pool's p50 job latency must be
      strictly below the isolated-per-job baseline's.
    """
    from benchmarks import fig15_multitenant

    rows = {r["label"]: r for r in rows_by_fig.get("fig15", [])}
    rate = smoke_kwargs["rates"][0]
    n_tenants = 4
    shared = rows.get(f"shared_pool_r{rate:g}_t{n_tenants}")
    isolated = rows.get(f"isolated_per_job_r{rate:g}_t{n_tenants}")
    if shared is None or isolated is None:
        return
    ps = shared["platform_stats"]
    if ps["jobs"] < 32 or len(ps["per_tenant"]) < 4:
        raise SystemExit(
            f"multitenant regression: smoke ran only {ps['jobs']} jobs "
            f"from {len(ps['per_tenant'])} tenants (>=32 from >=4 required)")
    if ps["failed"]:
        raise SystemExit(
            f"multitenant regression: {ps['failed']} smoke jobs failed")
    shared2, isolated2 = fig15_multitenant.shared_isolated_pair(
        n_jobs=smoke_kwargs["n_jobs"], rate=rate, n_tenants=n_tenants,
        max_concurrent_jobs=smoke_kwargs["max_concurrent_jobs"])
    for first, second in ((shared, shared2), (isolated, isolated2)):
        for field in ("wall_s", "p50_s", "p95_s", "p99_s",
                      "per_tenant_billed", "platform_stats"):
            if first[field] != second[field]:
                raise SystemExit(
                    f"multitenant regression: {first['label']} not "
                    f"deterministic across runs — {field} "
                    f"{first[field]!r} != {second[field]!r}")
    if not shared["p50_s"] < isolated["p50_s"]:
        raise SystemExit(
            f"multitenant regression: shared pool p50 {shared['p50_s']:.3f}s "
            f"not strictly below isolated-per-job {isolated['p50_s']:.3f}s")
    print(f"# multitenant gate OK: {ps['jobs']} jobs/"
          f"{len(ps['per_tenant'])} tenants deterministic; shared p50 "
          f"{shared['p50_s']:.3f}s vs isolated {isolated['p50_s']:.3f}s "
          f"(warm share {ps['warm_share'] * 100:.0f}% vs "
          f"{isolated['platform_stats']['warm_share'] * 100:.0f}%)",
          file=sys.stderr)


def _check_dataplane_gate(rows_by_fig: dict) -> None:
    """CI regression gate: on the smoke workload the optimized data
    plane (striping + batched round trips) must not be charged more
    simulated ms than the PR 1 data plane it replaced."""
    rows = rows_by_fig.get("fig08", [])
    striped = [r["charged_ms"] for r in rows
               if r["label"].startswith("wukong_striped@")]
    unstriped = [r["charged_ms"] for r in rows
                 if r["label"].startswith("wukong_unstriped@")]
    if not striped or not unstriped:
        return
    s, u = min(striped), min(unstriped)
    if s > u:
        raise SystemExit(
            f"data-plane regression: optimized Wukong charged {s:.1f}ms > "
            f"unoptimized {u:.1f}ms on the fig08 smoke workload"
        )
    saved = (1 - s / u) * 100
    print(f"# data-plane gate OK: charged {s:.1f}ms vs {u:.1f}ms "
          f"({saved:.1f}% saved)", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller problem sizes (CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes, near-zero simulated latency; "
                         "engine-regression gate for CI")
    ap.add_argument("--only", default=None, help="comma list, e.g. fig07")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        fig04_design_iterations,
        fig07_tree_reduction,
        fig08_gemm,
        fig09_svd_tall,
        fig10_svd_square,
        fig11_svc,
        fig12_factor_analysis,
        fig13_task_cdf,
        fig14_platform,
        fig15_multitenant,
        fig16_scaling,
        fig17_recovery,
        fig18_locality,
        fig19_streaming,
    )
    from benchmarks import common

    # One row per figure: (run fn, smoke kwargs, quick kwargs, full kwargs).
    # Adding a figure here covers all three modes, including CI's
    # bench-smoke gate.
    figs = {
        "fig04": (fig04_design_iterations.run,
                  dict(n=32, delays_ms=(0.0,)),
                  dict(n=128, delays_ms=(0.0, 50.0)),
                  dict(n=512, delays_ms=(0.0, 50.0, 100.0))),
        "fig07": (fig07_tree_reduction.run,
                  dict(n=32, delays_ms=(0.0,)),
                  dict(n=128, delays_ms=(0.0, 250.0)),
                  dict(n=512, delays_ms=(0.0, 250.0, 500.0))),
        "fig08": (fig08_gemm.run,
                  dict(sizes=((256, 128),)),
                  dict(sizes=((512, 128),)),
                  dict(sizes=((512, 128), (1024, 128), (2048, 128)))),
        "fig09": (fig09_svd_tall.run,
                  dict(row_sizes=(1024,)),
                  dict(row_sizes=(4096,)),
                  dict(row_sizes=(4096, 8192, 16384))),
        "fig10": (fig10_svd_square.run,
                  dict(sizes=(256,)),
                  dict(sizes=(512,)),
                  dict(sizes=(512, 1024, 2048, 4096))),
        "fig11": (fig11_svc.run,
                  dict(sample_sizes=(2048,)),
                  dict(sample_sizes=(8192,)),
                  dict(sample_sizes=(8192, 32768, 131072))),
        "fig12": (fig12_factor_analysis.run,
                  dict(n=32), dict(n=128), dict(n=512)),
        "fig13": (fig13_task_cdf.run,
                  dict(n=256), dict(n=1024), dict(n=2048)),
        "fig14": (fig14_platform.run,
                  dict(n=32, compute_ms=5.0, memory_sweep=(896, 1792),
                       pool_cap=4, pool_lanes=4, fanout_n=64,
                       fanout_burst=8, fanout_cap=16),
                  dict(n=128, compute_ms=100.0,
                       memory_sweep=(1024, 1792, 3584), pool_cap=16,
                       pool_lanes=8, fanout_n=512, fanout_burst=64,
                       fanout_cap=128),
                  dict()),
        "fig15": (fig15_multitenant.run,
                  dict(n_jobs=32, rates=(4.0,), tenant_counts=(4,),
                       max_concurrent_jobs=32),
                  dict(n_jobs=64, rates=(2.0, 8.0), tenant_counts=(2, 4),
                       max_concurrent_jobs=32),
                  dict()),
        # The simulator's scaling curve (PR 6). Smoke = the CI gate tiers
        # (10^5 engine tasks < 30 s); full adds the 10^6-task tier.
        "fig16": (fig16_scaling.run,
                  dict(),
                  dict(),
                  dict(micro_leaves=(1024, 4096, 16384),
                       engine_tiers=(8192, 131072, 1 << 20))),
        # Crash-recovery cost curves (durable control plane). The smoke
        # sweep crashes the dispatcher at all three protocol points and
        # gates on journal billing parity.
        "fig17": (fig17_recovery.run,
                  dict(n_jobs=12, rate=8.0, crash_ats=(2,),
                       max_concurrent_jobs=4),
                  dict(n_jobs=32, rate=8.0, crash_ats=(1, 4),
                       max_concurrent_jobs=8),
                  dict(n_jobs=64, crash_ats=(1, 4, 16))),
        # Locality series (multi-tier container cache vs cacheless) on
        # the two data-intensive shapes. Smoke = the CI locality gate
        # (cache strictly cheaper, tier-0 hits > 0, bit-identical
        # across runs); full adds a capacity sweep.
        "fig18": (fig18_locality.run,
                  dict(gemm_sizes=((512, 128),), tree_n=256),
                  dict(gemm_sizes=((512, 128),), tree_n=512),
                  dict(gemm_sizes=((512, 128), (1024, 128)), tree_n=1024,
                       capacities=(1 << 20, 4 << 20, 16 << 20))),
        # Steady-state streaming via the trigger bus (event-fired jobs,
        # windowed aggregation, dynamic-DAG parity, mid-stream crash).
        # Smoke = the CI streaming gate: >= 64 window jobs, all four
        # trigger sources live, bit-identical metrics across runs,
        # exactly-once fires across a dispatcher crash.
        "fig19": (fig19_streaming.run,
                  dict(n_events=400, crash_ats=(12,)),
                  dict(n_events=400, crash_ats=(12, 40)),
                  dict(n_events=1200, crash_ats=(12, 40, 120),
                       parity_n=64)),
    }
    mode = 0 if args.smoke else (1 if args.quick else 2)
    only = set(args.only.split(",")) if args.only else None
    rows_by_fig: dict[str, list[dict]] = {}
    print("name,us_per_call,derived")
    for name, (fn, *kwargs_by_mode) in figs.items():
        if only and name not in only:
            continue
        t0 = time.time()
        rows = fn(**kwargs_by_mode[mode])
        rows_by_fig[name] = rows
        common.emit(rows, name)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    snapshot = {
        "mode": ("smoke" if args.smoke else "quick" if args.quick else "full"),
        "schedule_generation": _time_schedule_generation(),
        "figures": {
            name: {r["label"]: _json_row(r) for r in rows}
            for name, rows in rows_by_fig.items()
        },
    }
    if "fig16" in rows_by_fig:
        # tasks vs host wall seconds — the PR 6 acceptance record
        # (fig16's wall_s is HOST seconds, unlike the simulated wall_s
        # of every other figure).
        snapshot["scaling_curve"] = fig16_scaling.scaling_curve(
            rows_by_fig["fig16"])
    path = os.path.normpath(RESULTS_JSON)
    with open(path, "w") as f:
        json.dump(snapshot, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", file=sys.stderr)

    if args.smoke:
        _check_dataplane_gate(rows_by_fig)
        _check_platform_gate(rows_by_fig, figs["fig14"][1])
        _check_multitenant_gate(rows_by_fig, figs["fig15"][1])
        if "fig16" in rows_by_fig:
            fig16_scaling.check_gates(rows_by_fig["fig16"])
        if "fig17" in rows_by_fig:
            fig17_recovery.check_gates(rows_by_fig["fig17"])
        if "fig18" in rows_by_fig:
            fig18_locality.check_gates(rows_by_fig["fig18"],
                                       **figs["fig18"][1])
        if "fig19" in rows_by_fig:
            fig19_streaming.check_gates(rows_by_fig["fig19"])


if __name__ == "__main__":
    main()
