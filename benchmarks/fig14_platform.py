"""Fig. 14 (beyond-paper): the stateful platform's cost/latency axes.

Three experiments on the stateful FaaS platform model (repro.platform):

1. **Cost-vs-latency Pareto** — sweep the Lambda memory size (CPU share
   is proportional to memory, so small containers are slow but cheap
   per GB-second... until longer billed durations eat the saving) and
   the keep-alive window (longer keep-alive converts cold starts into
   warm reuses at zero billing cost — keep-alive is charged to the
   *provider*, which is ServerMix's whole economic argument).
2. **Throttled mega-fan-out** — a 1024-leaf tree reduction against an
   account concurrency cap with a burst ramp: invocations beyond the
   limit get 429s and charged exponential backoff, reshaping the
   fan-out into waves (Lambada's observation that provider rate limits
   bound usable width).
3. **Fixed-cluster comparison** — the same workload on the serverful
   baseline, billed VM-hours for the makespan whether workers are busy
   or idle: pay-per-allocation vs the platform's pay-per-use.

Every number is deterministic under the virtual clock: two consecutive
runs produce bit-identical ``platform_stats`` including billed USD
(asserted by ``run.py --smoke``'s platform gate).
"""
from __future__ import annotations

from benchmarks import common
from repro.apps import tree_reduction_dag
from repro.core import CostModel, ServerfulConfig, ServerfulEngine
from repro.platform import PlatformConfig


def _pstat_row(label: str, r: dict, derived: str = "") -> dict:
    ps = r["platform_stats"]
    bits = [derived] if derived else []
    bits.append(f"billed=${ps.get('billed_usd', 0.0):.6f}")
    if ps.get("mode") == "pool":
        bits.append(f"cold={ps['cold_starts']}/warm={ps['warm_reuses']}"
                    f"/throttled={ps['throttle_events']}"
                    f"/peak={ps['peak_concurrency']}")
    r["label"] = label
    r["derived"] = " ".join(bits)
    return r


def warm_cold_pair(n: int, compute_ms: float, lanes: int,
                   keep_alive_s: float = 600.0) -> "tuple[dict, dict]":
    """The warm-pool-vs-all-cold-pool comparison the smoke gate asserts
    on. A small invoker-lane count staggers the leaf invocations (each
    lane charges ~50 ms serially per invoke), so early containers are
    already released when later invocations arrive — reuse without any
    throttling in the picture. The ONLY difference between the two runs
    is the keep-alive window: 0 reclaims every container immediately,
    making every invocation a cold start, so the cold run charges
    exactly the warm run plus the extra ``cold_start_ms`` draws."""
    dag = tree_reduction_dag(n, compute_ms=compute_ms)
    rows = []
    for label, keep in (("warm_pool", keep_alive_s), ("cold_pool", 0.0)):
        eng = common.wukong_platform(
            platform=PlatformConfig(keep_alive_s=keep),
            num_initial_invokers=lanes, num_proxy_invokers=lanes)
        r = common.timed(eng, dag)
        rows.append(_pstat_row(label, r, derived=f"keep={keep:g}s"))
    return rows[0], rows[1]


def run(n: int = 512,
        compute_ms: float = 250.0,
        memory_sweep: "tuple[int, ...]" = (512, 1024, 1792, 3584),
        keep_alive_s: float = 600.0,
        pool_cap: int = 64,
        pool_lanes: int = 8,
        fanout_n: int = 2048,
        fanout_burst: int = 128,
        fanout_cap: int = 384) -> list[dict]:
    rows: list[dict] = []
    dag = tree_reduction_dag(n, compute_ms=compute_ms)

    # -- 1. memory sweep: the cost-vs-latency Pareto frontier ---------------
    for mem in memory_sweep:
        eng = common.wukong_platform(platform=PlatformConfig(
            memory_mb=mem, keep_alive_s=keep_alive_s,
            account_concurrency=pool_cap, burst_concurrency=pool_cap))
        r = common.timed(eng, dag)
        rows.append(_pstat_row(f"pareto_mem{mem}", r,
                               derived=f"mem={mem}MB"))

    # -- keep-alive axis: warm pool vs all-cold pool ------------------------
    warm, cold = warm_cold_pair(n, compute_ms, pool_lanes,
                                keep_alive_s=keep_alive_s)
    rows += [warm, cold]

    # -- 2. throttled mega-fan-out ------------------------------------------
    eng = common.wukong_platform(platform=PlatformConfig(
        keep_alive_s=keep_alive_s, account_concurrency=fanout_cap,
        burst_concurrency=fanout_burst, burst_ramp_per_min=500.0))
    r = common.timed(eng, tree_reduction_dag(fanout_n,
                                             compute_ms=compute_ms))
    rows.append(_pstat_row(f"throttled_fanout{fanout_n // 2}", r,
                           derived=f"burst={fanout_burst}"
                                   f"->cap={fanout_cap}"))

    # -- 3. fixed-cluster cost comparison -----------------------------------
    eng = ServerfulEngine(ServerfulConfig(cost=CostModel()))
    r = common.timed(eng, dag)
    rows.append(_pstat_row("serverful_cluster", r,
                           derived="5xVM fixed"))
    return rows


def main() -> None:
    common.emit(run(), "fig14")


if __name__ == "__main__":
    main()
