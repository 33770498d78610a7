"""Multi-tenant orchestrator: shared substrate, fairness, leak fixes.

The tentpole properties:

- *substrate reuse*: back-to-back jobs on one engine instance — and two
  jobs on one shared substrate — produce equivalent reports, leave the
  store's pub/sub channel table empty, and keep the simclock worker
  cache bounded.
- *fair admission*: a flooding tenant cannot starve a light tenant of
  admission slots.
- *per-tenant billing isolation*: the shared account's per-tenant bill
  equals what each tenant would be billed on a private platform.
- *leak fixes*: subscriptions are released at job teardown
  (``_channels`` ends empty), and a failed (cancelled) job's in-flight
  executors stop at the next task boundary instead of walking — and
  billing — the rest of the DAG against the shared platform.
"""
import pytest

from repro.apps import tree_reduction_dag
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import (
    CacheConfig,
    CostModel,
    EngineConfig,
    FaultConfig,
    GraphBuilder,
    JobError,
    JobOrchestrator,
    JobRequest,
    OrchestratorConfig,
    ShardedKVStore,
    TenantSpec,
    WorkloadConfig,
    WukongEngine,
    generate_workload,
)
from repro.core.orchestrator import Substrate, _SIZE_LADDERS
from repro.core.simclock import simulated_compute
from repro.platform import PlatformConfig


def _engine_cfg(**kw):
    kw.setdefault("num_initial_invokers", 4)
    kw.setdefault("num_proxy_invokers", 4)
    return EngineConfig(**kw)


def _tr_workload(n_jobs=8, rate=4.0, tenants=None, seed=0, compute_ms=10.0):
    return WorkloadConfig(
        n_jobs=n_jobs, arrival_rate_per_s=rate, seed=seed,
        tenants=tenants or (TenantSpec("t-a", 1792), TenantSpec("t-b", 896)),
        app_mix=(("tree_reduction", 1.0),), compute_ms=compute_ms)


def _round(x, digits=12):
    return float(f"{x:.{digits}g}")


def _normalize(report):
    """A JobReport projected onto substrate-offset-independent form.

    Everything discrete must be bit-identical between two identical jobs
    on one shared substrate; timing floats are rounded to 12 significant
    digits because the shared clock does not restart between jobs, and
    float arithmetic at different absolute offsets differs in the last
    ulp (representation noise, not behavioral divergence)."""
    return {
        "results": {k: float(v[0]) for k, v in report.results.items()},
        "wall_s": _round(report.wall_s),
        "charged_ms": _round(report.charged_ms),
        "tasks": report.tasks,
        "executors": report.executors_invoked,
        "kv_stats": report.kv_stats,
        "metrics": [
            {k: (_round(v) if isinstance(v, float) else v)
             for k, v in m.items()}
            for m in report.metrics
        ],
    }


# ---------------------------------------------------------------------------
# KV namespaces (the per-job views of the shared store)
# ---------------------------------------------------------------------------


class TestKVNamespace:
    def test_namespaces_do_not_collide(self):
        kv = ShardedKVStore(n_shards=4)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        a.put("x", 1)
        b.put("x", 2)
        assert a.get("x") == 1 and b.get("x") == 2
        assert a.exists("x") and not a.exists("y")
        a.delete("x")
        assert not a.exists("x") and b.get("x") == 2

    def test_placement_ignores_registered_namespace_prefix(self):
        kv = ShardedKVStore(n_shards=10)
        kv.namespace("job7")
        kv.namespace("another")
        for key in ("tr-leaf-0", "gemm-C-1-2", "some/task"):
            base = kv._shard_index(key)
            assert kv._shard_index(f"job7::{key}") == base
            assert kv._shard_index(f"another::{key}") == base

    def test_placement_of_bare_keys_containing_separator_unchanged(self):
        import zlib

        # A direct store user whose own keys happen to contain "::" must
        # keep full-key placement: only REGISTERED namespace prefixes
        # are stripped, so 'layerA::out' and 'layerB::out' do not
        # collapse onto crc32('out')'s shard.
        kv = ShardedKVStore(n_shards=10)
        for key in ("layerA::out", "layerB::out"):
            assert kv._shard_index(key) == \
                zlib.crc32(key.encode()) % len(kv.shards)

    def test_per_view_stats_are_isolated(self):
        kv = ShardedKVStore(n_shards=4)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        a.put("x", b"abcd")
        a.get("x")
        b.put("y", b"zz")
        assert a.stats.puts == 1 and a.stats.gets == 1
        assert a.stats.bytes_written == 4 and a.stats.bytes_read == 4
        assert b.stats.puts == 1 and b.stats.gets == 0
        # the parent store aggregates everything
        assert kv.stats.puts == 2 and kv.stats.gets == 1

    def test_counters_and_deposit_are_namespaced(self):
        kv = ShardedKVStore(n_shards=4)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        a.register_counters({"c": 2})
        b.register_counters({"c": 2})
        assert a.increment_dependency("c", "e1") == 1
        assert b.counter_value("c") == 0
        count, missing = a.deposit_and_increment(
            "c", "e2", {"dep": 42}, expected=("other",))
        assert count == 2
        assert missing == ["other"]  # un-prefixed on the way out
        # the completing arrival skipped the write; the first arriver's
        # items went in under the view's names
        assert not a.exists("dep") or a.get("dep") == 42

    def test_pubsub_is_namespaced_and_unsubscribe_empties_channels(self):
        kv = ShardedKVStore(n_shards=2)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        qa, qb = a.subscribe("results"), b.subscribe("results")
        a.publish("results", {"from": "a"})
        assert qa.get(timeout=0.1) == {"from": "a"}
        assert qb.empty()
        assert kv.subscriber_count() == 2
        a.unsubscribe("results", qa)
        b.unsubscribe("results", qb)
        assert kv.subscriber_count() == 0
        assert kv._channels == {}

    def test_unsubscribe_is_idempotent(self):
        kv = ShardedKVStore(n_shards=2)
        q = kv.subscribe("ch")
        kv.unsubscribe("ch", q)
        kv.unsubscribe("ch", q)          # second release: no-op
        kv.unsubscribe("never", object())  # unknown channel: no-op
        assert kv._channels == {}

    def test_subscriber_count_is_view_scoped(self):
        kv = ShardedKVStore(n_shards=2)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        qb = b.subscribe("results")
        # job0 leaked nothing: its view must report zero even while
        # job1 holds a live subscription on the shared store
        assert a.subscriber_count() == 0
        assert b.subscriber_count() == 1
        assert kv.subscriber_count() == 1
        b.unsubscribe("results", qb)

    def test_purge_reclaims_namespaced_state(self):
        kv = ShardedKVStore(n_shards=4)
        a, b = kv.namespace("job0"), kv.namespace("job1")
        a.put("x", b"abcd")
        a.register_counters({"c": 2})
        a.increment_dependency("c", "e1")
        b.put("x", b"keep")
        removed = a.purge()
        assert removed == 1
        assert not a.exists("x")
        assert a.counter_value("c") == 0
        assert b.get("x") == b"keep"  # other jobs untouched
        assert sum(len(s.data) for s in kv.shards) == 1

    def test_publish_stops_fanning_to_dead_subscribers(self):
        kv = ShardedKVStore(n_shards=2)
        dead = kv.subscribe("ch")
        live = kv.subscribe("ch")
        kv.unsubscribe("ch", dead)
        kv.publish("ch", "msg")
        assert live.get(timeout=0.1) == "msg"
        assert dead.empty()


# ---------------------------------------------------------------------------
# Substrate reuse
# ---------------------------------------------------------------------------


class TestSubstrateReuse:
    def test_back_to_back_computes_on_one_engine_bit_identical(self):
        engine = WukongEngine(_engine_cfg())
        dag = tree_reduction_dag(32, compute_ms=25.0)
        r1 = engine.compute(dag)
        r2 = engine.compute(dag)
        (k1, v1), = r1.results.items()
        (k2, v2), = r2.results.items()
        assert k1 == k2 and float(v1[0]) == float(v2[0])
        assert r1.wall_s == r2.wall_s
        assert r1.charged_ms == r2.charged_ms
        assert r1.kv_stats == r2.kv_stats
        assert r1.metrics == r2.metrics
        assert r1.executors_invoked == r2.executors_invoked

    def test_sequential_jobs_on_shared_substrate_report_identically(self):
        cfg = _engine_cfg()
        substrate = Substrate(cfg, None)
        dag = tree_reduction_dag(32, compute_ms=25.0)
        reports = []
        with substrate.clock.actor():
            for i in range(3):
                sub = substrate.job_substrate(f"job{i}", "tenant-x")
                reports.append(WukongEngine(cfg).compute(dag, substrate=sub))
        n1, n2, n3 = (_normalize(r) for r in reports)
        assert n1 == n2 == n3
        assert n1["results"] == {
            "tr-3-0": tree_reduction_expected(32)}
        # teardown left the shared store clean: no leaked subscriptions
        assert substrate.kv.subscriber_count() == 0
        assert substrate.kv._channels == {}

    def test_shared_platform_carries_warmth_across_jobs(self):
        cfg = _engine_cfg(cost=CostModel(cold_start_ms=250.0))
        substrate = Substrate(cfg, PlatformConfig(keep_alive_s=600.0),
                              tenants=(TenantSpec("t", 1792),))
        dag = tree_reduction_dag(16, compute_ms=5.0)
        with substrate.clock.actor():
            sub0 = substrate.job_substrate("job0", "t")
            WukongEngine(cfg).compute(dag, substrate=sub0)
            cold_after_first = substrate.platform.pool.cold_starts
            sub1 = substrate.job_substrate("job1", "t")
            WukongEngine(cfg).compute(dag, substrate=sub1)
        # the second job found the first job's containers warm: no (or
        # almost no) additional cold starts
        assert substrate.platform.pool.cold_starts == cold_after_first
        assert substrate.platform.pool.warm_reuses > 0

    def test_prewarm_applies_to_tenant_functions(self):
        cfg = _engine_cfg(cost=CostModel(cold_start_ms=250.0))
        substrate = Substrate(
            cfg, PlatformConfig(keep_alive_s=600.0, prewarm=32),
            tenants=(TenantSpec("t-a", 1792), TenantSpec("t-b", 896)))
        dag = tree_reduction_dag(16, compute_ms=5.0)
        with substrate.clock.actor():
            WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job0", "t-a"))
            WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job1", "t-b"))
        # the prewarm knob warms each tenant's function, not just the
        # default single-job function name
        assert substrate.platform.pool.cold_starts == 0
        assert substrate.platform.pool.warm_reuses > 0

    def test_tenants_never_share_containers(self):
        cfg = _engine_cfg(cost=CostModel(cold_start_ms=250.0))
        substrate = Substrate(
            cfg, PlatformConfig(keep_alive_s=600.0),
            tenants=(TenantSpec("t-a"), TenantSpec("t-b")))
        dag = tree_reduction_dag(16, compute_ms=5.0)
        with substrate.clock.actor():
            WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job0", "t-a"))
            cold_a = substrate.platform.pool.cold_starts
            WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job1", "t-b"))
        # tenant B's function has its own (empty) pool: it provisions
        # cold even though tenant A's warm containers are sitting idle
        assert substrate.platform.pool.cold_starts > cold_a


# ---------------------------------------------------------------------------
# Job cancellation (the second leak fix)
# ---------------------------------------------------------------------------


def _failing_fanin_dag(chain_len=50, compute_ms=100.0):
    """A fan-in whose left leaf fails instantly while the right arm is a
    long chain of slow tasks: the job errors out almost immediately with
    the chain executor still near its start."""
    g = GraphBuilder()

    def boom():
        raise RuntimeError("boom")

    def slow_leaf():
        simulated_compute(compute_ms)
        return 1.0

    def slow_id(x):
        simulated_compute(compute_ms)
        return x

    bad = g.add(boom, name="bad-leaf")
    node = g.add(slow_leaf, name="chain-leaf")
    for i in range(chain_len):
        node = g.add(slow_id, node, name=f"chain-{i}")
    g.add(lambda a, b: (a, b), bad, node, name="root")
    return g.build()


class TestJobCancellation:
    def test_failed_job_stops_consuming_shared_capacity(self):
        chain_len, compute_ms = 50, 100.0
        cfg = _engine_cfg()
        substrate = Substrate(cfg, PlatformConfig(keep_alive_s=600.0),
                              tenants=(TenantSpec("t", 1792),))
        clock = substrate.clock
        with clock.actor():
            sub = substrate.job_substrate("job0", "t")
            with pytest.raises(JobError):
                WukongEngine(cfg).compute(_failing_fanin_dag(chain_len,
                                                             compute_ms),
                                          substrate=sub)
            # Give leaked work a full simulated minute to show itself.
            clock.charge(60_000.0)
            snap1 = substrate.platform.snapshot()
            clock.charge(60_000.0)
            snap2 = substrate.platform.snapshot()
        # no executor activity after the cancelled job wound down:
        # billing and pool counters are frozen
        assert snap1 == snap2
        # every concurrency slot was handed back
        assert substrate.platform.throttle.active == 0
        # the chain executor stopped at a task boundary instead of
        # walking (and billing) the whole chain against the dead job
        full_walk_ms = chain_len * compute_ms
        assert snap1["billed_duration_ms"] < full_walk_ms / 2
        # and teardown released every subscription
        assert substrate.kv.subscriber_count() == 0

    def test_teardown_releases_reservations_of_queued_bodies(self):
        # A runtime pool of ONE worker forces invocations to queue up
        # already holding a concurrency slot + container (reserved by
        # the invoker lane before runtime_pool.submit). A job timeout
        # then tears the job down with those wrapped bodies still
        # queued; dropping them would leak the reservations into the
        # shared account forever — they must run their release path.
        # Cheap invokes + a single runtime worker pinned on a 10 s task:
        # the other 7 leaf invocations are all queued (reservations
        # held) when the 0.5 s job timeout fires.
        cfg = _engine_cfg(max_concurrency=1, job_timeout_s=0.5,
                          cost=CostModel(invoke_ms=1.0, cold_start_ms=0.0))
        substrate = Substrate(cfg, PlatformConfig(keep_alive_s=600.0),
                              tenants=(TenantSpec("t", 1792),))
        clock = substrate.clock
        dag = tree_reduction_dag(16, compute_ms=10_000.0)
        with clock.actor():
            sub = substrate.job_substrate("job0", "t")
            with pytest.raises(JobError):
                WukongEngine(cfg).compute(dag, substrate=sub)
            clock.charge(60_000.0)  # let the cancelled job wind down
        assert substrate.platform.throttle.active == 0
        assert substrate.kv.subscriber_count() == 0

    def test_failed_job_leaves_channels_empty_self_contained(self):
        cfg = _engine_cfg(cost=CostModel())
        engine = WukongEngine(cfg)
        with pytest.raises(JobError):
            engine.compute(_failing_fanin_dag(chain_len=4, compute_ms=1.0))
        # self-contained path: can't reach the private kv afterwards, but
        # the substrate path above asserts the channel table; here we
        # assert the job still fails fast and deterministically
        r = None
        try:
            engine.compute(_failing_fanin_dag(chain_len=4, compute_ms=1.0))
        except JobError as exc:
            r = str(exc)
        assert r and "bad-leaf" in r


# ---------------------------------------------------------------------------
# Defensive platform snapshots (satellite 3)
# ---------------------------------------------------------------------------


class TestPlatformStatsAliasing:
    def test_two_reports_on_one_platform_never_alias(self):
        cfg = _engine_cfg(cost=CostModel(cold_start_ms=250.0))
        substrate = Substrate(cfg, PlatformConfig(keep_alive_s=600.0),
                              tenants=(TenantSpec("t", 1792),))
        dag = tree_reduction_dag(16, compute_ms=5.0)
        with substrate.clock.actor():
            r1 = WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job0", "t"))
            r2 = WukongEngine(cfg).compute(
                dag, substrate=substrate.job_substrate("job1", "t"))
        assert r1.platform_stats is not r2.platform_stats
        before = dict(r2.platform_stats)
        nested_before = {k: dict(v) for k, v in r2.platform_stats.items()
                         if isinstance(v, dict)}
        # vandalize report 1, including its nested per-tenant block
        r1.platform_stats["cold_starts"] = -999
        r1.platform_stats.clear()
        for v in nested_before.values():
            assert v  # sanity: the nested billing block exists
        assert r2.platform_stats == before
        for k, v in nested_before.items():
            assert r2.platform_stats[k] == v

    def test_snapshot_returns_fresh_structures(self):
        from repro.core.simclock import EventClock
        from repro.platform import FaaSPlatform

        platform = FaaSPlatform(PlatformConfig(), CostModel(),
                                EventClock())
        platform.configure_function("tenant-x", 896)
        platform.meter.add_invocation(10.0, memory_mb=896, key="tenant-x")
        s1, s2 = platform.snapshot(), platform.snapshot()
        assert s1 is not s2 and s1 == s2
        s1["billing_by_function"]["tenant-x"]["billed_usd"] = 1e9
        assert s2["billing_by_function"]["tenant-x"]["billed_usd"] != 1e9


# ---------------------------------------------------------------------------
# Workload generator
# ---------------------------------------------------------------------------


class TestWorkload:
    def test_deterministic_and_well_formed(self):
        cfg = WorkloadConfig(n_jobs=64, seed=7)
        jobs1, jobs2 = generate_workload(cfg), generate_workload(cfg)
        assert jobs1 == jobs2
        assert len(jobs1) == 64
        arrivals = [j.arrival_ms for j in jobs1]
        assert arrivals == sorted(arrivals)
        tenant_names = {t.name for t in cfg.tenants}
        for j in jobs1:
            assert j.tenant in tenant_names
            assert j.size in _SIZE_LADDERS[j.app]

    def test_seed_changes_the_stream(self):
        a = generate_workload(WorkloadConfig(n_jobs=16, seed=1))
        b = generate_workload(WorkloadConfig(n_jobs=16, seed=2))
        assert a != b

    def test_heavy_tail_prefers_small_sizes(self):
        jobs = generate_workload(WorkloadConfig(
            n_jobs=200, seed=3, app_mix=(("tree_reduction", 1.0),)))
        smallest = _SIZE_LADDERS["tree_reduction"][0]
        small = sum(1 for j in jobs if j.size == smallest)
        assert small > len(jobs) * 0.4  # ~55% expected at tail=0.45


# ---------------------------------------------------------------------------
# The orchestrator itself
# ---------------------------------------------------------------------------


class TestOrchestrator:
    def test_runs_workload_and_is_deterministic(self):
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=8),
                                 max_concurrent_jobs=4)
        r1 = JobOrchestrator(cfg).run()
        r2 = JobOrchestrator(cfg).run()
        assert r1.jobs == r1.completed == 8 and r1.failed == 0
        assert (r1.p50_s, r1.p95_s, r1.p99_s) == (r2.p50_s, r2.p95_s,
                                                  r2.p99_s)
        assert r1.billed_usd_total == r2.billed_usd_total
        assert r1.per_tenant == r2.per_tenant
        assert r1.job_records == r2.job_records
        assert r1.makespan_s > 0
        assert 0.0 <= r1.warm_share <= 1.0

    def test_rejects_engine_level_platform(self):
        with pytest.raises(ValueError):
            JobOrchestrator(OrchestratorConfig(
                engine=EngineConfig(platform=PlatformConfig())))

    def test_admission_gate_limits_running_jobs(self):
        # 6 jobs arriving at once through a 2-wide gate: completions must
        # overlap at most 2 at a time -> end times form >= 3 waves.
        jobs = [JobRequest(job_id=i, tenant="t", app="tree_reduction",
                           size=8, arrival_ms=0.0, compute_ms=10.0)
                for i in range(6)]
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(),
                                 max_concurrent_jobs=2)
        rep = JobOrchestrator(cfg).run(jobs)
        assert rep.completed == 6
        waits = sorted(r["queue_wait_s"] for r in rep.job_records)
        assert waits[0] == 0.0 and waits[-1] > 0.0  # later jobs queued

    def test_fair_admission_protects_light_tenant(self):
        # Tenant "heavy" floods 10 jobs at t=0; tenant "light" submits 2
        # shortly after. Through a 2-wide admission gate, fair admission
        # must admit light's jobs as soon as a slot frees; FIFO makes
        # them wait behind the whole flood.
        def jobs():
            out = [JobRequest(job_id=i, tenant="heavy",
                              app="tree_reduction", size=16,
                              arrival_ms=float(i), compute_ms=20.0)
                   for i in range(10)]
            out += [JobRequest(job_id=10 + i, tenant="light",
                               app="tree_reduction", size=16,
                               arrival_ms=20.0 + i, compute_ms=20.0)
                    for i in range(2)]
            return out

        def light_wait(fair):
            cfg = OrchestratorConfig(engine=_engine_cfg(),
                                     workload=_tr_workload(),
                                     max_concurrent_jobs=2,
                                     fair_admission=fair)
            rep = JobOrchestrator(cfg).run(jobs())
            assert rep.completed == 12
            waits = [r["queue_wait_s"] for r in rep.job_records
                     if r["tenant"] == "light"]
            return sum(waits) / len(waits)

        assert light_wait(True) < light_wait(False)

    def test_per_tenant_billing_isolation(self):
        wl = _tr_workload(n_jobs=10, tenants=(
            TenantSpec("t-big", 1792), TenantSpec("t-small", 896)))

        def run(isolated):
            cfg = OrchestratorConfig(engine=_engine_cfg(),
                                     workload=wl, max_concurrent_jobs=8,
                                     isolate_platform=isolated)
            return JobOrchestrator(cfg).run()

        shared, isolated = run(False), run(True)
        assert shared.completed == isolated.completed == 10
        # one account's per-tenant attribution == per-tenant private
        # platforms (billed duration is metered per invocation thread,
        # so shared-pool contention cannot leak across tenants)
        for tenant in shared.per_tenant:
            assert shared.per_tenant[tenant]["billed_usd"] == \
                pytest.approx(isolated.per_tenant[tenant]["billed_usd"],
                              rel=1e-12)
        # ...and the attribution is complete: tenant bills sum to the
        # account total
        assert sum(b["billed_usd"] for b in shared.per_tenant.values()) \
            == pytest.approx(shared.billed_usd_total, rel=1e-12)

    def test_shared_pool_beats_isolated_on_latency(self):
        wl = _tr_workload(n_jobs=12, rate=8.0)

        def run(isolated):
            cfg = OrchestratorConfig(
                engine=_engine_cfg(cost=CostModel(cold_start_ms=250.0)),
                workload=wl, max_concurrent_jobs=12,
                isolate_platform=isolated)
            return JobOrchestrator(cfg).run()

        shared, isolated = run(False), run(True)
        assert shared.warm_share > isolated.warm_share
        assert shared.p50_s < isolated.p50_s

    def test_failed_job_recorded_without_blocking_others(self):
        jobs = [JobRequest(job_id=0, tenant="t", app="tree_reduction",
                           size=16, arrival_ms=0.0, compute_ms=5.0),
                JobRequest(job_id=1, tenant="t", app="no-such-app",
                           size=16, arrival_ms=1.0, compute_ms=5.0),
                JobRequest(job_id=2, tenant="t", app="tree_reduction",
                           size=16, arrival_ms=2.0, compute_ms=5.0)]
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(),
                                 max_concurrent_jobs=2)
        rep = JobOrchestrator(cfg).run(jobs)
        assert rep.jobs == 3 and rep.completed == 2 and rep.failed == 1
        by_id = {r["job_id"]: r for r in rep.job_records}
        assert by_id[1]["error"] is not None
        assert by_id[0]["error"] is None and by_id[2]["error"] is None

    def test_store_memory_is_reclaimed_per_completed_job(self):
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=12),
                                 max_concurrent_jobs=3)
        orch = JobOrchestrator(cfg)
        rep = orch.run()
        assert rep.completed == 12
        kv = orch.last_substrate.kv
        # every completed job's namespace was purged: store memory is
        # O(concurrent jobs), not O(total traffic)
        assert sum(len(s.data) for s in kv.shards) == 0
        assert kv._counters == {} and kv._channels == {}

    def test_orchestrator_leaves_substrate_clean(self):
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=6),
                                 max_concurrent_jobs=3)
        orch = JobOrchestrator(cfg)
        rep = orch.run()
        assert rep.completed == 6
        # every job's waiter/proxy subscription was released: the job
        # records and per-tenant blocks exist, and nothing leaked into
        # the per-job channel table (asserted via a fresh run's store)
        substrate = Substrate(cfg.engine, None)
        with substrate.clock.actor():
            sub = substrate.job_substrate("probe", "t")
            WukongEngine(cfg.engine).compute(
                tree_reduction_dag(8, compute_ms=1.0), substrate=sub)
        assert substrate.kv._channels == {}

    def test_namespace_purged_when_job_dies_mid_flight(self):
        # A job whose every task attempt fails dies mid-flight with
        # executors still holding fan-in counters, channel subscriptions
        # and partial outputs in its namespace. The orchestrator's purge
        # must reclaim ALL of it: zero leaked keys, counters, channels.
        cfg = OrchestratorConfig(
            engine=_engine_cfg(faults=FaultConfig(task_failure_prob=1.0,
                                                  max_retries=1)),
            workload=_tr_workload(), max_concurrent_jobs=2)
        jobs = [JobRequest(job_id=i, tenant="t", app="tree_reduction",
                           size=16, arrival_ms=float(i), compute_ms=5.0)
                for i in range(3)]
        orch = JobOrchestrator(cfg)
        rep = orch.run(jobs)
        assert rep.failed == 3 and rep.completed == 0
        kv = orch.last_substrate.kv
        assert sum(len(s.data) for s in kv.shards) == 0
        assert kv._counters == {}
        assert kv._channels == {}


# ---------------------------------------------------------------------------
# Tenant tiers: priority admission, quotas, per-tier SLO accounting
# ---------------------------------------------------------------------------


class TestTenantTiers:
    def _jobs(self, spec):
        """spec: list of (tenant, arrival_ms); all jobs identical."""
        return [JobRequest(job_id=i, tenant=t, app="tree_reduction",
                           size=8, arrival_ms=at, compute_ms=10.0)
                for i, (t, at) in enumerate(spec)]

    def test_priority_admission_prefers_premium(self):
        # All jobs queued at t=0 behind a 1-wide gate: the premium
        # tenant's job must be admitted first despite arriving last in
        # job-id order, and the batch tenant's job last.
        tenants = (TenantSpec("std", 1024, tier="standard", priority=1),
                   TenantSpec("batch", 1024, tier="batch", priority=0),
                   TenantSpec("prem", 1024, tier="premium", priority=2))
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(tenants=tenants),
                                 max_concurrent_jobs=1)
        rep = JobOrchestrator(cfg).run(self._jobs(
            [("batch", 0.0), ("std", 0.0), ("prem", 0.0)]))
        assert rep.completed == 3
        order = [r["tenant"] for r in sorted(rep.job_records,
                                             key=lambda r: r["end_ms"])]
        assert order == ["prem", "std", "batch"]

    def test_per_tenant_quota_caps_concurrency(self):
        # Tenant "capped" may run at most 1 job at a time even though the
        # global gate is 4-wide: its 4 jobs must serialize (>= 4 waves),
        # while the uncapped tenant's jobs overlap freely.
        tenants = (TenantSpec("capped", 1024, max_concurrent_jobs=1),
                   TenantSpec("free", 1024))
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(tenants=tenants),
                                 max_concurrent_jobs=4)
        rep = JobOrchestrator(cfg).run(self._jobs(
            [("capped", 0.0)] * 4 + [("free", 0.0)] * 2))
        assert rep.completed == 6
        capped = sorted((r["admit_ms"], r["end_ms"])
                        for r in rep.job_records if r["tenant"] == "capped")
        for (_, prev_end), (next_admit, _) in zip(capped, capped[1:]):
            assert next_admit >= prev_end  # never two in flight
        # the quota never blocks the gate for the uncapped tenant: its
        # jobs are admitted in the first wave (waits are journaling-
        # scale milliseconds, not job-duration-scale serialization)
        free_waits = [r["queue_wait_s"] for r in rep.job_records
                      if r["tenant"] == "free"]
        capped_waits = sorted(r["queue_wait_s"] for r in rep.job_records
                              if r["tenant"] == "capped")
        assert max(free_waits) < 0.01
        assert capped_waits[-1] > max(free_waits)  # serialized behind quota

    def test_quota_does_not_deadlock_gate(self):
        # Only quota-blocked jobs queued: the admission loop must yield
        # (not spin or deadlock) until a slot frees.
        tenants = (TenantSpec("only", 1024, max_concurrent_jobs=1),)
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(tenants=tenants),
                                 max_concurrent_jobs=8)
        rep = JobOrchestrator(cfg).run(self._jobs([("only", 0.0)] * 3))
        assert rep.completed == 3

    def test_per_tier_report_block(self):
        tenants = (TenantSpec("std", 1792, tier="standard", priority=1,
                              slo_s=120.0),
                   TenantSpec("bat", 896, tier="batch", priority=0))
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=8,
                                                       tenants=tenants),
                                 max_concurrent_jobs=4)
        rep = JobOrchestrator(cfg).run()
        assert set(rep.per_tier) == {"standard", "batch"}
        for tier, block in rep.per_tier.items():
            assert block["jobs"] > 0 or block["failed"] > 0
            assert block["p50_s"] <= block["p95_s"] <= block["p99_s"]
        assert rep.per_tier["standard"]["slo_s"] == 120.0
        assert rep.per_tier["batch"]["slo_s"] is None
        assert rep.per_tier["batch"]["slo_violations"] == 0
        # tier billing is the sum of its tenants' bills
        assert rep.per_tier["standard"]["billed_usd"] == \
            pytest.approx(rep.per_tenant["std"]["billed_usd"], rel=1e-12)
        # per-tenant blocks now carry tier + tail percentiles
        assert rep.per_tenant["std"]["tier"] == "standard"
        assert "p95_s" in rep.per_tenant["std"]
        assert "p99_s" in rep.per_tenant["std"]

    def test_slo_violations_counted(self):
        # An absurdly tight SLO: every completed job violates it.
        tenants = (TenantSpec("tight", 1024, tier="rt", priority=1,
                              slo_s=1e-9),)
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=4,
                                                       tenants=tenants),
                                 max_concurrent_jobs=4)
        rep = JobOrchestrator(cfg).run()
        assert rep.per_tier["rt"]["slo_violations"] == rep.completed > 0


# ---------------------------------------------------------------------------
# Container-cache coherence (locality PR): purge reaches caches, and a
# recycled warm container never serves a stale bare key across jobs
# ---------------------------------------------------------------------------


def _deposit(cache, key, value="v", nbytes=8):
    for _ in cache.deposit_g(key, value, nbytes):
        pass


class TestContainerCacheCoherence:
    def test_drop_namespace_invalidates_container_cached_entries(self):
        cfg = _engine_cfg()
        substrate = Substrate(
            cfg, PlatformConfig(keep_alive_s=600.0, cache=CacheConfig()),
            tenants=(TenantSpec("t", 1792),))
        cache = substrate.platform.caches.cache_for("t", 1)
        k_dead = substrate.kv.namespace("job0").qualified_key("x")
        k_live = substrate.kv.namespace("job1").qualified_key("x")
        _deposit(cache, k_dead)
        _deposit(cache, k_live)
        # the shared-substrate purge listener reclaims job0's entry from
        # the container cache along with its KV objects
        substrate.kv.drop_namespace("job0")
        assert not cache.contains(k_dead)
        assert cache.contains(k_live)

    def test_recycled_warm_container_serves_no_stale_bare_key(self):
        # Two sequential jobs use the SAME bare task keys with different
        # values, on one shared platform with a long keep-alive — the
        # second job's fan-in completer re-fetches "left"/"right", and a
        # bare-keyed container cache would hand it the first job's
        # objects. Store-qualified cache keys (+ purge invalidation)
        # must keep the results exact.
        def dag_with(v):
            g = GraphBuilder()
            a = g.add((lambda x: (lambda: x))(v), name="left")
            b = g.add((lambda x: (lambda: x * 10))(v), name="right")
            g.add(lambda x, y: x + y, a, b, name="root")
            return g.build()

        cfg = _engine_cfg(cost=CostModel(cold_start_ms=250.0))
        substrate = Substrate(
            cfg, PlatformConfig(keep_alive_s=600.0, cache=CacheConfig()),
            tenants=(TenantSpec("t", 1792),))
        with substrate.clock.actor():
            sub0 = substrate.job_substrate("job0", "t")
            r0 = WukongEngine(cfg).compute(dag_with(1), substrate=sub0)
            sub0.kv.purge()  # what the orchestrator does on completion
            sub1 = substrate.job_substrate("job1", "t")
            r1 = WukongEngine(cfg).compute(dag_with(2), substrate=sub1)
        assert r0.results == {"root": 11}
        assert r1.results == {"root": 22}  # never 11, 12, or 21
        # and the purge reclaimed job0's entries from every cache
        reg = substrate.platform.caches
        prefix = substrate.kv.namespace("job0").qualified_key("")
        assert reg.invalidate_prefix(prefix) == 0  # nothing left to drop

    def test_orchestrator_reports_cache_and_stays_deterministic(self):
        cfg = OrchestratorConfig(
            engine=_engine_cfg(),
            platform=PlatformConfig(keep_alive_s=600.0,
                                    cache=CacheConfig()),
            workload=_tr_workload(n_jobs=6), max_concurrent_jobs=4)
        r1 = JobOrchestrator(cfg).run()
        r2 = JobOrchestrator(cfg).run()
        assert r1.completed == 6 and r1.failed == 0
        assert r1.cache and r1.cache["deposits"] > 0
        assert r1.cache == r2.cache
        assert r1.job_records == r2.job_records

    def test_cacheless_orchestrator_report_has_empty_cache_block(self):
        cfg = OrchestratorConfig(engine=_engine_cfg(),
                                 workload=_tr_workload(n_jobs=4),
                                 max_concurrent_jobs=4)
        rep = JobOrchestrator(cfg).run()
        assert rep.completed == 4
        assert rep.cache == {}
