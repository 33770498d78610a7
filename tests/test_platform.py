"""Stateful FaaS platform model (repro.platform): pool, throttle, billing.

The tentpole properties:

- *keep-alive expiry* is driven by the engine clock: a container
  released and re-acquired within the window is warm; past it, cold.
- *determinism*: two identical runs produce bit-identical
  ``platform_stats`` — cold/warm counts, throttle events, peak
  concurrency, and billed USD.
- *throttle-then-retry*: a burst wider than the concurrency cap gets
  429-throttled, retries with charged exponential backoff, and still
  completes correctly with peak concurrency never above the cap.
- *billing follows the charges*: billed duration is metered from the
  invocation's simulated charges (not wall deltas), so billing is as
  deterministic as the charges (pinned in tests/test_determinism.py).
"""
import dataclasses

import pytest

from repro.apps import tree_reduction_dag
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import (
    CostModel,
    EngineConfig,
    ParallelInvokerEngine,
    PlatformConfig,
    ServerfulConfig,
    ServerfulEngine,
    WukongEngine,
)
from repro.core.simclock import EventClock, charge_meter
from repro.platform import (
    BillingMeter,
    ComputeScaledClock,
    ConcurrencyThrottle,
    ContainerPool,
    FaaSPlatform,
)


# ---------------------------------------------------------------------------
# Component-level: pool / throttle / billing / config
# ---------------------------------------------------------------------------


class TestContainerPool:
    def test_keep_alive_expiry_on_virtual_clock(self):
        clock = EventClock()
        pool = ContainerPool(PlatformConfig(keep_alive_s=1.0), clock)
        with clock.actor():
            cid, cold = pool.acquire("f")
            assert cold
            pool.release("f", cid)
            clock.charge(500.0)  # 0.5 simulated s: still warm
            cid2, cold2 = pool.acquire("f")
            assert not cold2 and cid2 == cid
            pool.release("f", cid2)
            clock.charge(1500.0)  # past the 1 s keep-alive: expired
            cid3, cold3 = pool.acquire("f")
            assert cold3 and cid3 != cid
        assert pool.cold_starts == 2
        assert pool.warm_reuses == 1
        assert pool.expired == 1

    def test_zero_keep_alive_never_reuses(self):
        clock = EventClock()
        pool = ContainerPool(PlatformConfig(keep_alive_s=0.0), clock)
        with clock.actor():
            for _ in range(3):
                cid, cold = pool.acquire("f")
                assert cold
                pool.release("f", cid)
        assert pool.cold_starts == 3 and pool.warm_reuses == 0

    def test_lifo_reuse_and_per_function_isolation(self):
        clock = EventClock()
        pool = ContainerPool(PlatformConfig(keep_alive_s=60.0), clock)
        with clock.actor():
            a, _ = pool.acquire("f")
            b, _ = pool.acquire("f")
            pool.release("f", a)
            clock.charge(1.0)
            pool.release("f", b)
            got, cold = pool.acquire("f")
            assert got == b and not cold  # most recently released first
            other, cold_other = pool.acquire("g")
            assert cold_other  # "g" never saw a release

    def test_prewarm(self):
        clock = EventClock()
        pool = ContainerPool(PlatformConfig(keep_alive_s=60.0), clock)
        pool.prewarm("f", 2)
        with clock.actor():
            _, cold1 = pool.acquire("f")
            _, cold2 = pool.acquire("f")
            _, cold3 = pool.acquire("f")
        assert (cold1, cold2, cold3) == (False, False, True)


class TestConcurrencyThrottle:
    def test_burst_ramp_limit(self):
        clock = EventClock()
        th = ConcurrencyThrottle(PlatformConfig(
            account_concurrency=10, burst_concurrency=2,
            burst_ramp_per_min=60.0), clock)
        with clock.actor():
            assert th.limit_now() == 2
            assert th.try_reserve() and th.try_reserve()
            assert not th.try_reserve()  # 429
            assert th.throttle_events == 1
            clock.charge(1000.0)  # +1 simulated s -> +1 ramped slot
            assert th.limit_now() == 3
            assert th.try_reserve()
            clock.charge(600_000.0)  # ramp far past the account cap
            assert th.limit_now() == 10

    def test_backoff_schedule_is_charged_exponential(self):
        clock = EventClock()
        th = ConcurrencyThrottle(PlatformConfig(
            throttle_backoff_base_ms=100.0,
            throttle_backoff_cap_ms=350.0), clock)
        assert [th.backoff_ms(k) for k in range(4)] == [100.0, 200.0,
                                                        350.0, 350.0]

    def test_release_frees_slot(self):
        clock = EventClock()
        th = ConcurrencyThrottle(PlatformConfig(
            account_concurrency=1, burst_concurrency=1), clock)
        assert th.try_reserve()
        assert not th.try_reserve()
        th.release()
        assert th.try_reserve()
        assert th.peak_concurrency == 1


class TestBillingMeter:
    def test_granularity_rounds_up(self):
        meter = BillingMeter(PlatformConfig(billing_granularity_ms=100.0))
        assert meter.add_invocation(1.0) == 100.0
        assert meter.add_invocation(100.0) == 100.0
        assert meter.add_invocation(100.1) == 200.0
        assert meter.snapshot()["billed_duration_ms"] == 400.0

    def test_usd_formula(self):
        cfg = PlatformConfig(memory_mb=1024, price_per_request_usd=1e-6,
                             price_per_gb_s_usd=2e-5)
        meter = BillingMeter(cfg)
        meter.add_invocation(2000.0)  # 2 s at 1 GB -> 2 GB-s
        snap = meter.snapshot()
        assert snap["billed_requests"] == 1
        assert snap["billed_gb_s"] == pytest.approx(2.0)
        assert snap["billed_usd"] == pytest.approx(1e-6 + 2 * 2e-5)

    def test_empty(self):
        snap = BillingMeter(PlatformConfig()).snapshot()
        assert snap["billed_requests"] == 0
        assert snap["billed_usd"] == 0.0


class TestConfig:
    def test_compute_scale(self):
        assert PlatformConfig(memory_mb=896).compute_scale == 2.0
        assert PlatformConfig(memory_mb=3584).compute_scale == 0.5
        assert PlatformConfig().compute_scale == 1.0

    def test_validation(self):
        with pytest.raises(ValueError):
            PlatformConfig(memory_mb=0)
        with pytest.raises(ValueError):
            PlatformConfig(burst_concurrency=0)
        with pytest.raises(ValueError):
            PlatformConfig(throttle_backoff_base_ms=0.0)

    def test_scaled_clock_charges_scaled(self):
        clock = EventClock()
        scaled = ComputeScaledClock(clock, 2.0)
        with clock.actor():
            scaled.charge(100.0)
            assert clock.now_ms() == 200.0
        assert scaled.now_ms() == clock.now_ms()  # delegation


class TestChargeMeter:
    def test_accumulates_this_threads_charges(self):
        clock = EventClock()
        acc = [0.0]
        with charge_meter(acc):
            clock.charge(30.0)
            clock.charge(12.5)
        clock.charge(99.0)  # outside the meter
        assert acc[0] == 42.5

    def test_nesting_restores_outer(self):
        clock = EventClock()
        outer, inner = [0.0], [0.0]
        with charge_meter(outer):
            clock.charge(10.0)
            with charge_meter(inner):
                clock.charge(5.0)
            clock.charge(1.0)
        assert inner[0] == 5.0
        assert outer[0] == 11.0  # inner charges land innermost only


# ---------------------------------------------------------------------------
# Engine-level: the platform threaded through the invocation path
# ---------------------------------------------------------------------------


def _tr(n=32, compute_ms=5.0):
    return tree_reduction_dag(n, compute_ms=compute_ms)


def _warm_cfg(**platform_kw):
    # Few invoker lanes stagger invocations so container reuse can occur.
    return EngineConfig(
        cost=CostModel(cold_start_ms=250.0),
        platform=PlatformConfig(**platform_kw),
        num_initial_invokers=4, num_proxy_invokers=4,
    )


class TestPlatformEngine:
    def test_warm_reuse_and_correct_result(self):
        rep = WukongEngine(_warm_cfg(keep_alive_s=600.0)).compute(_tr())
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(32)
        ps = rep.platform_stats
        assert ps["mode"] == "pool"
        assert ps["warm_reuses"] > 0
        assert ps["cold_starts"] + ps["warm_reuses"] == ps["invocations"]
        assert ps["billed_requests"] == ps["invocations"]
        assert ps["billed_usd"] > 0

    def test_warm_reuse_deterministic_across_runs(self):
        cfg = _warm_cfg(keep_alive_s=600.0)
        r1 = WukongEngine(cfg).compute(_tr())
        r2 = WukongEngine(cfg).compute(_tr())
        assert r1.platform_stats == r2.platform_stats
        assert r1.wall_s == r2.wall_s
        assert r1.charged_ms == r2.charged_ms

    def test_warm_pool_charges_strictly_less_than_cold(self):
        warm = WukongEngine(_warm_cfg(keep_alive_s=600.0)).compute(_tr())
        cold = WukongEngine(_warm_cfg(keep_alive_s=0.0)).compute(_tr())
        assert warm.platform_stats["warm_reuses"] > 0
        assert cold.platform_stats["warm_reuses"] == 0
        assert warm.charged_ms < cold.charged_ms

    def test_throttle_then_retry_completes_under_burst(self):
        # 16 leaf invocations against a cap of 3: most of the burst is
        # 429-throttled and retried with charged backoff; the job still
        # resolves correctly and concurrency never exceeds the cap.
        cfg = EngineConfig(platform=PlatformConfig(
            account_concurrency=3, burst_concurrency=3,
            burst_ramp_per_min=0.0, keep_alive_s=600.0))
        rep = WukongEngine(cfg).compute(_tr())
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(32)
        ps = rep.platform_stats
        assert ps["throttle_events"] > 0
        assert ps["peak_concurrency"] <= 3
        # throttling staggered the burst into waves -> containers reused
        assert ps["warm_reuses"] > 0

    def test_throttling_charges_backoff(self):
        free = EngineConfig(platform=PlatformConfig(keep_alive_s=0.0))
        capped = EngineConfig(platform=PlatformConfig(
            account_concurrency=3, burst_concurrency=3,
            burst_ramp_per_min=0.0, keep_alive_s=0.0))
        r_free = WukongEngine(free).compute(_tr())
        r_capped = WukongEngine(capped).compute(_tr())
        assert r_free.platform_stats["throttle_events"] == 0
        assert r_capped.charged_ms > r_free.charged_ms

    def test_memory_knob_trades_cost_for_latency(self):
        small = WukongEngine(_warm_cfg(memory_mb=896)).compute(_tr())
        large = WukongEngine(_warm_cfg(memory_mb=1792)).compute(_tr())
        # half the memory -> compute runs 2x slower -> longer makespan
        assert small.wall_s > large.wall_s
        # ...but the GB-s product keeps billed cost in the same ballpark
        # (more ms x less GB), slightly cheaper for the small container
        # because the unscaled I/O time is billed over less memory.
        assert small.platform_stats["billed_usd"] < \
            large.platform_stats["billed_usd"]

    def test_prewarmed_pool_skips_all_cold_starts(self):
        rep = WukongEngine(_warm_cfg(prewarm=32)).compute(_tr())
        ps = rep.platform_stats
        assert ps["cold_starts"] == 0
        assert ps["warm_reuses"] == ps["invocations"]

    def test_centralized_engine_platform(self):
        rep = ParallelInvokerEngine(
            cost=CostModel(cold_start_ms=250.0),
            platform=PlatformConfig(keep_alive_s=600.0),
        ).compute(_tr(16, compute_ms=2.0))
        ps = rep.platform_stats
        assert ps["mode"] == "pool"
        # one Lambda per task: 15 invocations, with warm reuse across
        # the sequential dependency waves
        assert ps["invocations"] == 15
        assert ps["warm_reuses"] > 0


class TestReportingSatellites:
    def test_legacy_mode_surfaces_invoker_cold_starts(self):
        # The InvokerPool.cold_starts counter (previously incremented but
        # never reported) now rides JobReport.platform_stats.
        cfg = EngineConfig(cost=CostModel(warm_fraction=0.5,
                                          cold_start_ms=100.0))
        rep = WukongEngine(cfg).compute(_tr())
        ps = rep.platform_stats
        assert ps["mode"] == "legacy"
        assert ps["invocations"] > 0
        assert 0 < ps["cold_starts"] <= ps["invocations"]

    def test_legacy_all_warm_has_zero_cold_starts(self):
        rep = WukongEngine(EngineConfig()).compute(_tr())
        assert rep.platform_stats["cold_starts"] == 0

    def test_serverful_fixed_cluster_billing(self):
        cfg = ServerfulConfig(n_vms=5, vm_price_per_hour_usd=0.3712)
        rep = ServerfulEngine(cfg).compute(_tr())
        ps = rep.platform_stats
        assert ps["mode"] == "serverful"
        assert ps["billed_usd"] == pytest.approx(
            5 * 0.3712 * rep.wall_s / 3600.0)

    def test_platform_config_is_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            PlatformConfig().memory_mb = 512


class TestPlatformFacade:
    def test_wrap_meters_and_releases(self):
        clock = EventClock()
        platform = FaaSPlatform(PlatformConfig(keep_alive_s=60.0),
                                CostModel(), clock)
        with clock.actor():
            assert platform.try_reserve()
            cid, cold = platform.acquire()
            assert cold
            body = platform.wrap("executor", cid, lambda: clock.charge(7.5))
            body()
            snap = platform.snapshot()
            assert snap["billed_requests"] == 1
            assert snap["billed_duration_ms"] == 8.0  # ceil to 1 ms
            assert platform.throttle.active == 0
            # container back in the pool, warm
            _, cold2 = platform.acquire()
            assert not cold2

    def test_cancel_returns_slot_and_container_unbilled(self):
        clock = EventClock()
        platform = FaaSPlatform(PlatformConfig(keep_alive_s=60.0),
                                CostModel(), clock)
        with clock.actor():
            assert platform.try_reserve()
            cid, _ = platform.acquire()
            platform.cancel("executor", cid)
            assert platform.throttle.active == 0
            assert platform.snapshot()["billed_requests"] == 0
