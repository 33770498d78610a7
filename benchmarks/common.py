"""Shared benchmark scaffolding: engine presets + the simulated cost model.

All paper-figure benchmarks run the *real* engines on *real* DAGs with
jitted JAX payloads; only the FaaS substrate costs (invocation latency,
KV transfer, TCP handling, per-task compute duration) are simulated.

Everything runs on the deterministic discrete-event clock
(repro.core.simclock's ``EventClock``): simulated seconds cost zero
wall time, results and charged ms are bit-identical across runs, and
``wall_s`` in every row is the simulated makespan. The paper's
*relative* claims are the reproduction targets (absolute AWS seconds
are not reproducible in this container — DESIGN.md §1).
"""
from __future__ import annotations

import os
from typing import Any

from repro.core import (
    ALL_PASSES,
    CostModel,
    EngineConfig,
    OptimizeConfig,
    ParallelInvokerEngine,
    PubSubEngine,
    ServerfulConfig,
    ServerfulEngine,
    StrawmanEngine,
    WukongEngine,
)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


# Effective per-core throughput of the simulated cluster. Task compute
# duration = analytic_flops / GFLOPS_SIM simulated ms, charged on the
# engine clock like every other simulated latency. This is how the
# paper's compute-heavy regime (where Lambda's elastic core count beats
# a 25-core cluster) is emulated on a single-core container.
GFLOPS_SIM = float(os.environ.get("REPRO_GFLOPS_SIM", "0.02")) * 1e9
# default calibrated so a 128^3 block product ~ 210 ms simulated (the
# paper's sub-second task regime) and simulated compute >> the real
# single-core jnp time of the small blocks


def ms_per_flop() -> float:
    """Simulated ms charged per analytic flop."""
    return 1e3 / GFLOPS_SIM


def wukong(**kw: Any) -> WukongEngine:
    return WukongEngine(EngineConfig(cost=CostModel(), **kw))


def wukong_optimized(optimize: OptimizeConfig = ALL_PASSES,
                     **kw: Any) -> WukongEngine:
    """WUKONG with the DAG compiler pipeline (optimized-vs-unoptimized
    series; pass an ``OptimizeConfig`` for single-pass ablations)."""
    return WukongEngine(EngineConfig(cost=CostModel(), optimize=optimize, **kw))


# -- data-plane factor series (striping + batched round trips) --------------
# The paper's data-intensive workloads move GB-scale blocks; at this
# container's toy block sizes the default 600 MB/s lane makes transfers
# negligible next to invoke_ms, so the striped-vs-unstriped comparison
# emulates the paper's regime by scaling the per-shard lane down. Both
# series share the regime — the ONLY difference between them is the two
# data-plane factors, so the comparison isolates exactly what §V-B-style
# factor analysis requires.
DATAPLANE_KV_MBPS = 5.0          # per-shard lane in the emulated regime
DATAPLANE_STRIPE_BYTES = 8 << 10  # stripe target: a 64 KiB GEMM block -> 8


def wukong_dataplane(**kw: Any) -> WukongEngine:
    """Optimized WUKONG with the PR 2 data plane ON: striped large
    objects + batched (mget / counter-registration) round trips."""
    c = CostModel(kv_bandwidth_mbps=DATAPLANE_KV_MBPS,
             stripe_threshold_bytes=DATAPLANE_STRIPE_BYTES)
    return WukongEngine(EngineConfig(cost=c, optimize=ALL_PASSES,
                                     batch_kv_round_trips=True, **kw))


def wukong_dataplane_off(**kw: Any) -> WukongEngine:
    """Optimized WUKONG with the PR 1 data plane: one shard lane per
    object (striping off), one round trip per key (batching off). Same
    emulated regime as ``wukong_dataplane`` — the ablation baseline."""
    c = CostModel(kv_bandwidth_mbps=DATAPLANE_KV_MBPS,
             stripe_threshold_bytes=0)
    return WukongEngine(EngineConfig(cost=c, optimize=ALL_PASSES,
                                     batch_kv_round_trips=False, **kw))


def wukong_locality(cache: "Any | None" = None,
                    optimize: OptimizeConfig = ALL_PASSES,
                    invokers: int = 8, **kw: Any) -> WukongEngine:
    """WUKONG on the stateful platform in the emulated data-intensive
    regime, with an optional container cache (``CacheConfig``) — the
    fig18 locality series. Same KV regime as ``wukong_dataplane``, so
    the cacheless arm is the PR 2 data plane and the cached arms isolate
    exactly the multi-tier cache + locality-aware placement."""
    from repro.platform import PlatformConfig

    c = CostModel(kv_bandwidth_mbps=DATAPLANE_KV_MBPS,
             stripe_threshold_bytes=DATAPLANE_STRIPE_BYTES,
             cold_start_ms=250.0)
    return WukongEngine(EngineConfig(
        cost=c, optimize=optimize, batch_kv_round_trips=True,
        num_initial_invokers=invokers, num_proxy_invokers=invokers,
        platform=PlatformConfig(keep_alive_s=600.0, cache=cache), **kw))


# -- stateful platform presets (fig14: warm pool / throttling / billing) ----


def wukong_platform(platform: "Any | None" = None,
                    **kw: Any) -> WukongEngine:
    """Optimized WUKONG on the stateful platform model (repro.platform):
    warm-container pool + concurrency throttle + billing meter. Pass a
    ``PlatformConfig`` to set the memory / keep-alive / concurrency
    knobs; cost-model overrides ride ``kw['cost']``."""
    from repro.platform import PlatformConfig

    c = kw.pop("cost", None) or CostModel(cold_start_ms=250.0)
    return WukongEngine(EngineConfig(
        cost=c, optimize=ALL_PASSES,
        platform=platform or PlatformConfig(), **kw))


def parallel_invoker_optimized(n: int = 20) -> ParallelInvokerEngine:
    """Centralized best-iteration with the DAG compiler (chain fusion
    shrinks its one-Lambda-per-task graph)."""
    return ParallelInvokerEngine(cost=CostModel(), num_invokers=n,
                                 optimize=ALL_PASSES)


def strawman() -> StrawmanEngine:
    return StrawmanEngine(cost=CostModel())


def pubsub() -> PubSubEngine:
    return PubSubEngine(cost=CostModel())


def parallel_invoker(n: int = 20) -> ParallelInvokerEngine:
    return ParallelInvokerEngine(cost=CostModel(), num_invokers=n)


def serverful_ec2() -> ServerfulEngine:
    # paper: five t2.2xlarge VMs x five workers
    return ServerfulEngine(ServerfulConfig(
        cost=CostModel(), n_workers=25, worker_bandwidth_mbps=1000.0,
        n_vms=5, vm_price_per_hour_usd=0.3712))


def serverful_laptop() -> ServerfulEngine:
    # paper: two-core i5 laptop, four workers — owned hardware, so the
    # fixed-cluster billing model charges no VM-hours
    return ServerfulEngine(ServerfulConfig(
        cost=CostModel(), n_workers=4, worker_bandwidth_mbps=4000.0,
        n_vms=0, vm_price_per_hour_usd=0.0))


def timed(engine, dag, repeats: int = 1) -> dict[str, Any]:
    """Run and report simulated-environment wall seconds (mean over
    repeats) plus engine counters. ``wall_s`` is the simulated makespan,
    which host-side work (XLA compilation of the task payloads) cannot
    perturb."""
    walls = []
    rep = None
    for _ in range(repeats):
        rep = engine.compute(dag)
        walls.append(rep.wall_s)
    return {
        "wall_s": sum(walls) / len(walls),
        "min_s": min(walls),
        "max_s": max(walls),
        "tasks": rep.tasks,
        "executors": rep.executors_invoked,
        "kv_bytes": rep.kv_stats["bytes_read"] + rep.kv_stats["bytes_written"],
        "kv_stats": rep.kv_stats,
        "charged_ms": rep.charged_ms,
        "metrics": rep.metrics,
        "platform_stats": rep.platform_stats,
    }


def emit(rows: list[dict[str, Any]], name: str) -> None:
    """Print the standard CSV block for run.py."""
    for r in rows:
        us = r["wall_s"] * 1e6
        derived = r.get("derived", "")
        print(f"{name}/{r['label']},{us:.0f},{derived}")
