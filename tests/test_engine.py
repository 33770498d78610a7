"""Engine correctness: all five engines, faults, stragglers, counters.

The central property: every engine computes exactly what a sequential
topological evaluation computes, for any DAG.
"""
import operator
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip without the dev extra
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    EngineConfig,
    FaultConfig,
    GraphBuilder,
    JobError,
    ParallelInvokerEngine,
    PubSubEngine,
    ServerfulEngine,
    StrawmanEngine,
    WukongEngine,
)
from repro.core.dag import TaskRef


def seq_eval(dag):
    vals = {}
    for k in dag.topological_order():
        t = dag.tasks[k]
        args = [vals[a.key] if isinstance(a, TaskRef) else a
                for a in t.args]
        kwargs = {kk: vals[v.key] if isinstance(v, TaskRef) else v
                  for kk, v in t.kwargs.items()}
        vals[k] = t.fn(*args, **kwargs)
    return {k: vals[k] for k in dag.roots}


def tree_dag(n):
    g = GraphBuilder()
    level = [g.add((lambda v: (lambda: v))(i), name=f"leaf-{i}")
             for i in range(n)]
    d = 0
    while len(level) > 1:
        level = [g.add(operator.add, level[i], level[i + 1],
                       name=f"add-{d}-{i // 2}")
                 for i in range(0, len(level), 2)]
        d += 1
    return g.build()


def random_dag(seed: int, n: int):
    rng = random.Random(seed)
    g = GraphBuilder()
    refs = []
    for i in range(n):
        k = rng.randint(0, min(4, len(refs)))
        deps = rng.sample(refs, k) if k else []
        if deps:
            refs.append(g.add(lambda *xs: sum(xs) + 1, *deps, name=f"n{i}"))
        else:
            refs.append(g.add((lambda v: (lambda: v))(i), name=f"n{i}"))
    return g.build()


ENGINES = [
    ("wukong", lambda: WukongEngine()),
    ("strawman", lambda: StrawmanEngine()),
    ("pubsub", lambda: PubSubEngine()),
    ("parallel_invoker", lambda: ParallelInvokerEngine()),
    ("serverful", lambda: ServerfulEngine()),
]


@pytest.mark.parametrize("name,factory", ENGINES)
def test_all_engines_tree(name, factory):
    dag = tree_dag(64)
    rep = factory().compute(dag)
    assert rep.results == seq_eval(dag)


@pytest.mark.parametrize("name,factory", ENGINES)
def test_all_engines_random_dag(name, factory):
    dag = random_dag(42, 50)
    assert factory().compute(dag).results == seq_eval(dag)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 60))
def test_wukong_matches_sequential_eval(seed, n):
    """Property: decentralized scheduling == topological evaluation."""
    dag = random_dag(seed, n)
    rep = WukongEngine().compute(dag)
    assert rep.results == seq_eval(dag)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_wukong_paper_counter_mode(seed):
    """Plain INCR counters (the paper's exact protocol) are equivalent
    when there are no retries."""
    dag = random_dag(seed, 40)
    rep = WukongEngine(EngineConfig(counter_mode="paper")).compute(dag)
    assert rep.results == seq_eval(dag)


def test_wide_fanout_uses_proxy():
    g = GraphBuilder()
    src = g.add(lambda: 3, name="src")
    outs = [g.add(lambda x, i=i: x * i, src, name=f"m{i}")
            for i in range(32)]
    g.add(lambda *xs: sum(xs), *outs, name="total")
    dag = g.build()
    rep = WukongEngine(EngineConfig(proxy_threshold=8)).compute(dag)
    assert rep.results["total"] == 3 * sum(range(32))


def test_executor_count_matches_paper_fig6():
    """Figure 6 walkthrough uses exactly 3 executors (E1, E2, E3)."""
    g = GraphBuilder()
    t1 = g.add(lambda: 1, name="T1")
    t2 = g.add(lambda: 2, name="T2")
    t3 = g.add(lambda x: x + 10, t2, name="T3")
    t5 = g.add(lambda x: x * 2, t3, name="T5")
    g.add(operator.add, t1, t3, name="T4")
    g.add(operator.add, TaskRef("T4"), t5, name="T6")
    rep = WukongEngine().compute(g.build())
    assert rep.results == {"T6": 37}
    assert rep.executors_invoked == 3


class TestFaultTolerance:
    def test_retries_recover(self):
        """seed=21 is a verified recoverable injection under the
        process-stable fault hash (failures at attempts 0/1 on disjoint
        keys, none at the final attempt), so completion is guaranteed
        regardless of executor arrival order."""
        dag = tree_dag(32)
        cfg = EngineConfig(faults=FaultConfig(
            task_failure_prob=0.04, max_retries=2, seed=21))
        rep = WukongEngine(cfg).compute(dag)
        assert rep.results == seq_eval(dag)
        # the report's fault counters audit the recovery work: this seed
        # injects failures, every one is retried, and attempts account
        # for every task plus every retry
        assert rep.fault_stats["injected_failures"] > 0
        assert rep.fault_stats["task_retries"] == \
            rep.fault_stats["injected_failures"]
        assert rep.fault_stats["task_attempts"] >= \
            len(dag.tasks) + rep.fault_stats["injected_failures"]

    def test_exhausted_retries_fail_loudly(self):
        g = GraphBuilder()
        g.add(lambda: 1, name="only")
        cfg = EngineConfig(faults=FaultConfig(
            task_failure_prob=1.0, max_retries=2, seed=0),
            job_timeout_s=20.0)
        with pytest.raises(JobError, match="failed"):
            WukongEngine(cfg).compute(g.build())

    def test_task_exception_propagates(self):
        g = GraphBuilder()

        def boom():
            raise RuntimeError("kaboom")

        g.add(boom, name="bad")
        with pytest.raises(JobError, match="kaboom"):
            WukongEngine().compute(g.build())

    def test_speculative_straggler_duplicates_are_safe(self):
        dag = tree_dag(16)
        cfg = EngineConfig(
            faults=FaultConfig(straggler_prob=0.2,
                               straggler_slowdown_ms=2000,
                               speculative_threshold_ms=200, seed=5),
            speculative_poll_s=0.005,
        )
        rep = WukongEngine(cfg).compute(dag)
        assert rep.results == seq_eval(dag)
        # speculation fired (that is what this config provokes) and each
        # duplicate is counted — the billing-overhead audit trail
        assert rep.fault_stats["speculative_duplicates"] > 0

    def test_edge_set_counters_safe_under_retries(self):
        """Retries must not double-fire fan-ins. With the paper's plain
        INCR counters they CAN (the documented hazard, why a retry run
        cannot be asserted in that mode); edge_set counters close the
        hole, so the job must complete correctly. seed=6 is a verified
        recoverable injection under the process-stable fault hash
        (failures at attempt 0 but none at later attempts), so completion
        is guaranteed regardless of executor arrival order."""
        dag = tree_dag(8)
        cfg = EngineConfig(
            counter_mode="edge_set",
            faults=FaultConfig(task_failure_prob=0.1, max_retries=2,
                               seed=6))
        rep = WukongEngine(cfg).compute(dag)
        assert rep.results == seq_eval(dag)


class TestFaultStatsReporting:
    """JobReport.fault_stats: the per-job retry/failure audit trail."""

    def test_clean_run_reports_zero_fault_activity(self):
        dag = tree_dag(16)
        rep = WukongEngine().compute(dag)
        stats = rep.fault_stats
        assert stats["task_attempts"] == len(dag.tasks)
        for field in ("injected_failures", "task_retries",
                      "speculative_duplicates", "throttle_retries",
                      "tasks_resumed"):
            assert stats[field] == 0

    def test_throttle_retries_counted(self):
        # 2-slot account + eager invokers: 429s are inevitable, and each
        # charged backoff round trip is counted in the report.
        from repro.platform import PlatformConfig
        dag = tree_dag(32)
        cfg = EngineConfig(
            platform=PlatformConfig(account_concurrency=2,
                                    burst_concurrency=2),
            num_initial_invokers=8)
        rep = WukongEngine(cfg).compute(dag)
        assert rep.results == seq_eval(dag)
        assert rep.fault_stats["throttle_retries"] > 0

    def test_deterministic_across_runs(self):
        dag = tree_dag(32)
        cfg = EngineConfig(faults=FaultConfig(
            task_failure_prob=0.04, max_retries=2, seed=21))
        r1 = WukongEngine(cfg).compute(dag)
        r2 = WukongEngine(cfg).compute(dag)
        assert r1.fault_stats == r2.fault_stats


class TestFaultConfigValidation:
    """Satellite: every bad knob is rejected at construction, not
    discovered as a silent mid-run misbehavior."""

    @pytest.mark.parametrize("field,value", [
        ("task_failure_prob", -0.1),
        ("task_failure_prob", 1.1),
        ("straggler_prob", -0.5),
        ("straggler_prob", 2.0),
        ("max_retries", -1),
        ("retry_backoff_base_ms", -1.0),
        ("straggler_slowdown_ms", -10.0),
        ("max_backoff_ms", 0.0),
        ("max_backoff_ms", -5.0),
        ("speculative_threshold_ms", 0.0),
        ("speculative_threshold_ms", -1.0),
        ("orchestrator_crash_point", "bogus"),
        ("orchestrator_crash_at", 0),
    ])
    def test_bad_field_raises(self, field, value):
        with pytest.raises(ValueError, match=field):
            FaultConfig(**{field: value})

    def test_boundary_values_accepted(self):
        FaultConfig(task_failure_prob=0.0)
        FaultConfig(task_failure_prob=1.0)
        FaultConfig(straggler_prob=1.0, max_retries=0,
                    retry_backoff_base_ms=0.0, straggler_slowdown_ms=0.0)
        FaultConfig(speculative_threshold_ms=float("inf"))
        FaultConfig(orchestrator_crash_point=None)
        FaultConfig(orchestrator_crash_point="dispatch",
                    orchestrator_crash_at=1)


class TestRetryBackoffCap:
    """Satellite: exponential retry backoff saturates at max_backoff_ms
    instead of letting 2**k dominate the simulated makespan."""

    def test_exponential_growth_then_cap(self):
        from repro.core.faults import exponential_backoff_ms
        assert exponential_backoff_ms(100.0, 0, cap_ms=1e4) == 100.0
        assert exponential_backoff_ms(100.0, 3, cap_ms=1e4) == 800.0
        assert exponential_backoff_ms(100.0, 20, cap_ms=1e4) == 1e4
        assert exponential_backoff_ms(0.0, 50, cap_ms=1e4) == 0.0

    def test_injector_applies_configured_cap(self):
        from repro.core.faults import FaultInjector
        inj = FaultInjector(FaultConfig(retry_backoff_base_ms=1000.0,
                                        max_backoff_ms=4000.0))
        assert [inj.retry_backoff_ms(k) for k in range(5)] == \
            [1000.0, 2000.0, 4000.0, 4000.0, 4000.0]

    def test_cap_bounds_charged_retry_delay(self):
        # seed=21 on tree_dag(32) is the verified recoverable injection
        # (see test_retries_recover). Same faults, huge backoff base:
        # a tight cap must make the charged makespan strictly smaller
        # than a loose one, by at least the backoff delta it shaves.
        dag = tree_dag(32)

        def run(cap_ms):
            cfg = EngineConfig(faults=FaultConfig(
                task_failure_prob=0.04, max_retries=2, seed=21,
                retry_backoff_base_ms=5e4, max_backoff_ms=cap_ms))
            rep = WukongEngine(cfg).compute(dag)
            assert rep.results == seq_eval(dag)
            return rep

        tight, loose = run(10.0), run(1e6)
        assert tight.fault_stats["task_retries"] == \
            loose.fault_stats["task_retries"] > 0
        assert tight.charged_ms < loose.charged_ms


class TestCostAccounting:
    def test_invocations_charged(self):
        dag = tree_dag(16)
        rep = WukongEngine().compute(dag)
        # 16 leaf schedules; every invocation costs >= invoke_ms
        assert rep.executors_invoked >= 16
        assert rep.charged_ms >= rep.executors_invoked * 50.0

    def test_locality_reduces_kv_traffic(self):
        """WUKONG's executor-local caching must move fewer KV bytes than
        the centralized engine on the same chain-heavy DAG."""
        g = GraphBuilder()
        cur = g.add(lambda: list(range(2048)), name="start")
        for i in range(20):  # a pure chain: all local for WUKONG
            cur = g.add(lambda x: x, cur, name=f"c{i}")
        dag = g.build()
        w = WukongEngine().compute(dag)
        c = PubSubEngine().compute(dag)
        wb = w.kv_stats["bytes_read"] + w.kv_stats["bytes_written"]
        cb = c.kv_stats["bytes_read"] + c.kv_stats["bytes_written"]
        assert wb < cb / 5
