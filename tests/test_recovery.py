"""Durable control plane: state machine, journal, crash → replay recovery.

The acceptance properties (ISSUE 7):

- the job state machine is monotonic and replay-safe: duplicate /
  regressive transitions are no-ops, the first terminal state wins, and
  replaying the journal rebuilds the exact state;
- journal primitives are charged KV operations and are reclaimed with
  their namespace;
- for every injected orchestrator crash point, a recovered run
  completes all jobs, journaled-complete
  jobs are returned from the journal (never re-executed), and their
  billed USD is bit-identical to the uncrashed baseline.
"""
import dataclasses

import pytest

from repro.core import (
    ADMITTED,
    COMPLETED,
    EngineConfig,
    FAILED,
    FaultConfig,
    JobOrchestrator,
    JobStateMachine,
    OrchestratorConfig,
    OrchestratorCrashed,
    PENDING,
    RUNNING,
    ShardedKVStore,
    TenantSpec,
    WorkloadConfig,
)
from repro.core.statemachine import InvalidTransition

CRASH_POINTS = ("admit", "dispatch", "complete")


def _engine_cfg(**kw):
    kw.setdefault("num_initial_invokers", 2)
    kw.setdefault("num_proxy_invokers", 2)
    kw.setdefault("max_concurrency", 64)
    return EngineConfig(**kw)


def _workload(n_jobs=6, seed=3):
    return WorkloadConfig(
        n_jobs=n_jobs, arrival_rate_per_s=8.0, seed=seed,
        tenants=(TenantSpec("t-a", 1792, tier="standard", priority=1,
                            slo_s=120.0),
                 TenantSpec("t-b", 896, tier="batch", priority=0)),
        app_mix=(("tree_reduction", 1.0),), compute_ms=5.0)


def _orch_cfg(crash_point=None, crash_at=2, **kw):
    faults = FaultConfig(orchestrator_crash_point=crash_point,
                         orchestrator_crash_at=crash_at)
    kw.setdefault("engine", _engine_cfg())
    kw.setdefault("workload", _workload())
    kw.setdefault("max_concurrent_jobs", 3)
    return OrchestratorConfig(faults=faults, **kw)


# ---------------------------------------------------------------------------
# State machine semantics
# ---------------------------------------------------------------------------


class TestJobStateMachine:
    def test_monotonic_forward_transitions(self):
        kv = ShardedKVStore(n_shards=4)
        m = JobStateMachine(kv.namespace("__control__"))
        for state in (PENDING, ADMITTED, RUNNING, COMPLETED):
            assert kv.clock.run(m.record_g(0, state)) is True
        assert m.state(0) == COMPLETED

    def test_duplicates_and_regressions_are_noops(self):
        kv = ShardedKVStore(n_shards=4)
        m = JobStateMachine(kv.namespace("__control__"))
        kv.clock.run(m.record_g(0, RUNNING))
        before = m.journal_len()
        # duplicate, regression, and a second terminal after the first:
        assert kv.clock.run(m.record_g(0, RUNNING)) is False
        assert kv.clock.run(m.record_g(0, PENDING)) is False
        kv.clock.run(m.record_g(0, COMPLETED))
        assert kv.clock.run(m.record_g(0, FAILED)) is False  # first wins
        assert m.state(0) == COMPLETED
        # no-ops are not journaled (replay must not grow the log)
        assert m.journal_len() == before + 1

    def test_unknown_state_raises(self):
        kv = ShardedKVStore(n_shards=4)
        m = JobStateMachine(kv.namespace("__control__"))
        with pytest.raises(InvalidTransition):
            kv.clock.run(m.record_g(0, "EXPLODED"))

    def test_replay_rebuilds_state_and_payloads(self):
        kv = ShardedKVStore(n_shards=4)
        ctrl = kv.namespace("__control__")
        m = JobStateMachine(ctrl)
        kv.clock.run(m.record_g(0, PENDING, payload={"app": "x"}))
        kv.clock.run(m.record_g(0, RUNNING))
        kv.clock.run(m.record_g(1, PENDING, payload={"app": "y"}))
        kv.clock.run(m.record_g(1, COMPLETED, payload={"latency_s": 2.0}))
        fresh = JobStateMachine(ctrl)
        assert kv.clock.run(fresh.replay_g()) == 4
        assert fresh.jobs() == m.jobs() == {0: RUNNING, 1: COMPLETED}
        assert fresh.payload(0, PENDING) == {"app": "x"}
        assert fresh.payload(1, COMPLETED) == {"latency_s": 2.0}
        # replay is idempotent: a second replay changes nothing
        assert kv.clock.run(fresh.replay_g()) == 4
        assert fresh.jobs() == {0: RUNNING, 1: COMPLETED}

    def test_transitions_are_charged(self):
        kv = ShardedKVStore(n_shards=4)
        m = JobStateMachine(kv.namespace("__control__"))
        t0 = kv.clock.charged_ms
        kv.clock.run(m.record_g(0, PENDING, payload={"app": "x"}))
        assert kv.clock.charged_ms > t0
        assert kv.stats.journal_appends == 1
        t1 = kv.clock.charged_ms
        kv.clock.run(m.replay_g())
        assert kv.clock.charged_ms > t1
        assert kv.stats.journal_scans == 1


# ---------------------------------------------------------------------------
# Journal primitives (kvstore layer)
# ---------------------------------------------------------------------------


class TestJournalPrimitives:
    def test_append_scan_order_and_len(self):
        kv = ShardedKVStore(n_shards=4)
        assert kv.journal_append("log", {"n": 0}) == 0
        assert kv.journal_append("log", {"n": 1}) == 1
        assert kv.journal_scan("log") == [{"n": 0}, {"n": 1}]
        assert kv.journal_len("log") == 2
        assert kv.journal_scan("absent") == []
        assert kv.journal_len("absent") == 0

    def test_journals_live_outside_shard_data(self):
        kv = ShardedKVStore(n_shards=4)
        kv.journal_append("log", {"n": 0})
        assert sum(len(s.data) for s in kv.shards) == 0

    def test_scan_cost_grows_with_journal(self):
        kv = ShardedKVStore(n_shards=4)
        kv.journal_append("log", b"x" * 1000)
        t0 = kv.clock.charged_ms
        kv.journal_scan("log")
        short = kv.clock.charged_ms - t0
        for _ in range(8):
            kv.journal_append("log", b"x" * 1000)
        t1 = kv.clock.charged_ms
        kv.journal_scan("log")
        assert kv.clock.charged_ms - t1 > short

    def test_namespaced_journals_are_prefixed_and_purged(self):
        kv = ShardedKVStore(n_shards=4)
        ns = kv.namespace("ctrl")
        ns.journal_append("log", {"n": 0})
        assert ns.journal_len("log") == 1
        assert kv.journal_len("ctrl::log") == 1
        assert kv.journal_len("log") == 0
        assert ns.stats.journal_appends == 1
        kv.drop_namespace("ctrl")
        assert ns.journal_len("log") == 0
        assert ns.journal_scan("log") == []


# ---------------------------------------------------------------------------
# Crash → replay recovery (the tentpole acceptance sweep)
# ---------------------------------------------------------------------------


def _baseline():
    rep = JobOrchestrator(_orch_cfg()).run()
    assert rep.completed == rep.jobs
    return rep


class TestCrashRecovery:
    @pytest.mark.parametrize("point", CRASH_POINTS)
    def test_recovered_run_completes_with_billing_parity(self, point):
        base = _baseline()
        base_by_id = {r["job_id"]: r for r in base.job_records}

        orch = JobOrchestrator(_orch_cfg(crash_point=point))
        rep = orch.run_with_recovery()

        assert rep.crashes == 1
        assert rep.completed == rep.jobs == base.jobs
        assert rep.failed == 0
        # every journaled-complete job is returned from the journal with
        # billed USD (and latency) bit-identical to the uncrashed
        # baseline — no double execution, no double billing
        from_journal = [r for r in rep.job_records if r.get("from_journal")]
        for rec in from_journal:
            b = base_by_id[rec["job_id"]]
            assert rec["billed_usd"] == b["billed_usd"]
            assert rec["latency_s"] == b["latency_s"]
        # per-tenant billed USD over the already-completed jobs matches
        # the baseline sum exactly
        for tenant in {r["tenant"] for r in from_journal}:
            assert sum(r["billed_usd"] for r in from_journal
                       if r["tenant"] == tenant) == \
                sum(base_by_id[r["job_id"]]["billed_usd"]
                    for r in from_journal if r["tenant"] == tenant)
        if point in ("admit", "dispatch"):
            # crash hits while jobs are in flight: recovery re-admits
            assert rep.recovered_jobs > 0

    def test_complete_crash_leaves_journaled_jobs_untouched(self):
        """Crash between COMPLETED journal and purge: the job's bill in
        the shared meter must not grow during recovery (its work is
        never re-executed)."""
        orch = JobOrchestrator(_orch_cfg(crash_point="complete", crash_at=2))
        rep = orch.run_with_recovery()
        from_journal = [r for r in rep.job_records if r.get("from_journal")]
        assert from_journal  # the 2nd completion was journaled pre-crash
        platform = orch.last_substrate.platform
        for rec in from_journal:
            metered = platform.meter.job_snapshot(
                f"job{rec['job_id']}")["billed_usd"]
            assert metered == rec["billed_usd"]

    def test_recovery_purges_orphaned_namespace(self):
        """The 'complete' crash orphans the finished job's namespace in
        the shared store; replay recovery must reclaim it (and every
        later job's) so the store ends empty."""
        orch = JobOrchestrator(_orch_cfg(crash_point="complete"))
        rep = orch.run_with_recovery()
        assert rep.completed == rep.jobs
        kv = orch.last_substrate.kv
        assert sum(len(s.data) for s in kv.shards) == 0
        assert kv._counters == {}
        assert kv._channels == {}
        # the control journal itself survives (it IS the durable state)
        assert kv.journal_len("__control__::journal") > 0

    def test_crash_is_deterministic_on_event_substrate(self):
        cfg = _orch_cfg(crash_point="dispatch")
        r1 = JobOrchestrator(cfg).run_with_recovery()
        r2 = JobOrchestrator(cfg).run_with_recovery()
        assert r1.job_records == r2.job_records
        assert r1.crashes == r2.crashes == 1
        assert r1.recovered_jobs == r2.recovered_jobs

    def test_run_raises_without_supervision(self):
        orch = JobOrchestrator(_orch_cfg(crash_point="admit", crash_at=1))
        with pytest.raises(OrchestratorCrashed) as ei:
            orch.run()
        assert ei.value.point == "admit"
        # the substrate carried on the exception is the run's substrate
        assert ei.value.substrate is orch.last_substrate

    def test_manual_recover_on_fresh_instance(self):
        """Recovery needs nothing from the dead process: a brand-new
        orchestrator + the crashed substrate's journal completes the
        workload."""
        cfg = _orch_cfg(crash_point="dispatch")
        crashed = JobOrchestrator(cfg)
        with pytest.raises(OrchestratorCrashed) as ei:
            crashed.run()
        fresh = JobOrchestrator(cfg)
        rep = fresh.recover(ei.value.substrate, injector=ei.value.injector)
        assert rep.completed == rep.jobs
        assert rep.recovered_jobs > 0

    def test_resume_skips_durable_outputs(self):
        """A 'complete'-point crash leaves earlier jobs' in-flight peers
        mid-run; their recovery re-admission must reuse durable task
        outputs (tasks_resumed > 0) rather than recompute everything."""
        rep = JobOrchestrator(
            _orch_cfg(crash_point="complete")).run_with_recovery()
        assert rep.tasks_resumed > 0

    def test_injected_crash_fires_exactly_once(self):
        """The injector's occurrence counter spans generations: the
        recovered dispatcher passes the same crash point again without
        re-crashing."""
        cfg = _orch_cfg(crash_point="admit", crash_at=1)
        rep = JobOrchestrator(cfg).run_with_recovery()
        assert rep.crashes == 1
        assert rep.completed == rep.jobs


# ---------------------------------------------------------------------------
# Orchestrator-level FaultConfig plumbing
# ---------------------------------------------------------------------------


class TestCrashConfigValidation:
    def test_unknown_crash_point_rejected(self):
        with pytest.raises(ValueError):
            FaultConfig(orchestrator_crash_point="reboot")

    def test_crash_at_must_be_positive(self):
        with pytest.raises(ValueError):
            FaultConfig(orchestrator_crash_point="admit",
                        orchestrator_crash_at=0)

    def test_tenant_spec_validation(self):
        with pytest.raises(ValueError):
            TenantSpec("t", memory_mb=0)
        with pytest.raises(ValueError):
            TenantSpec("t", max_concurrent_jobs=0)
        with pytest.raises(ValueError):
            TenantSpec("t", slo_s=0.0)

    def test_engine_faults_and_orchestrator_faults_are_independent(self):
        cfg = _orch_cfg(crash_point="admit")
        assert cfg.engine.faults.orchestrator_crash_point is None
        assert cfg.faults.orchestrator_crash_point == "admit"
        # dataclasses.replace round-trips the new fields
        again = dataclasses.replace(cfg.faults, orchestrator_crash_at=3)
        assert again.orchestrator_crash_at == 3
