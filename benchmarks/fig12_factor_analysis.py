"""Fig. 12: factor analysis — contribution of each optimization.

Cumulative versions from strawman to full WUKONG. Paper claims the
decentralization of Task Executors is the single largest factor; then
parallel invokers, the KV-proxy for large fan-outs, pub/sub, and giving
each KV shard its own VM (NIC decontention).

Beyond-paper axis: the *warm Lambda pool* (paper §V-A warms a pool so
invocations skip container cold starts). ``7_cold_pool`` re-runs the
full WUKONG configuration with a cold-start distribution — only
``warm_fraction`` of invocations hit a warm container; the rest pay
``cold_start_ms`` — plus seeded lognormal invoke-latency jitter, the
latency-distribution realism the virtual clock makes deterministic.
The 6→7 gap is the warm pool's contribution.
"""
from __future__ import annotations

from benchmarks import common
from repro.apps import tree_reduction_dag
from repro.core import (
    CostModel,
    EngineConfig,
    ParallelInvokerEngine,
    PubSubEngine,
    StrawmanEngine,
    WukongEngine,
)


def run(n: int = 512, delay_ms: float = 20.0,
        payload_bytes: int = 4 << 20,
        cold_warm_fraction: float = 0.5,
        cold_invoke_sigma: float = 0.25) -> list[dict]:
    # wide fan-outs (n/2 leaves) + 4MB edge payloads: exercises the proxy
    # and the per-shard NIC contention the paper's factors 5/6 target
    dagf = lambda: tree_reduction_dag(
        n, compute_ms=delay_ms, payload_bytes=payload_bytes)
    rows = []
    # Factors are cumulative; "own VM per KV shard" arrived LAST in the
    # paper, so every earlier version runs with colocated shards.
    steps = [
        ("1_strawman", StrawmanEngine(
            cost=CostModel(), colocate_kv_shards=True)),
        ("2_pubsub", PubSubEngine(
            cost=CostModel(), colocate_kv_shards=True)),
        ("3_parallel_invoker", ParallelInvokerEngine(
            cost=CostModel(), colocate_kv_shards=True)),
        # decentralized Task Executors (static schedules + local caches):
        ("4_decentralized", WukongEngine(EngineConfig(
            cost=CostModel(), use_proxy=False, colocate_kv_shards=True))),
        ("5_plus_proxy", WukongEngine(EngineConfig(
            cost=CostModel(), use_proxy=True, colocate_kv_shards=True))),
        ("6_sharded_vms", WukongEngine(EngineConfig(
            cost=CostModel(), use_proxy=True, colocate_kv_shards=False))),
        # ...and what full WUKONG would cost WITHOUT the warm pool:
        ("7_cold_pool", WukongEngine(EngineConfig(
            cost=CostModel(warm_fraction=cold_warm_fraction,
                             invoke_sigma=cold_invoke_sigma),
            use_proxy=True, colocate_kv_shards=False))),
    ]
    for label, eng in steps:
        r = common.timed(eng, dagf())
        r["label"] = label
        r["derived"] = f"delay={delay_ms:g}ms"
        rows.append(r)
    return rows


def main() -> None:
    common.emit(run(), "fig12")


if __name__ == "__main__":
    main()
