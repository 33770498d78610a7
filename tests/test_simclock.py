"""The simulation clock (PR 3, PR 6): primitives, determinism, scale.

The tentpole properties:

- *the effect protocol*: frames suspend on ``charge``/``get``/
  ``acquire``/``wait``/``sleep`` effects, wake in FIFO and
  (deadline, seq) order, and threads outside the frames bridge in
  through ``run_effects`` and ``actor()``;
- *determinism*: two identical runs produce bit-identical ``wall_s`` /
  ``charged_ms`` / metrics / results — even with latency jitter, cold
  starts, and fault injection enabled (all draws are seeded, and the
  clock serializes actors in a reproducible order);
- *scale decoupling*: simulated seconds cost zero wall time, so a
  4096-leaf tree reduction (~8k tasks, minutes of simulated time)
  completes in seconds of wall time, and ``job_timeout_s`` means
  *simulated* seconds (a timeout fires instantly in wall time).
"""
import queue
import threading
import time

import pytest

from repro.apps import tree_reduction_dag
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import (
    CostModel,
    EngineConfig,
    FaultConfig,
    JobError,
    WukongEngine,
)
from repro.core.simclock import EventClock, run_effects


# ---------------------------------------------------------------------------
# Clock primitives (the effect protocol)
# ---------------------------------------------------------------------------


class TestEventClockPrimitives:
    def test_charge_advances_and_run_returns(self):
        clock = EventClock()

        def main():
            yield ("charge", 250.0)
            yield ("charge", 125.0)
            return clock.now_ms()

        assert clock.run(main()) == 375.0
        assert clock.charged_ms == 375.0

    def test_sleepers_wake_in_deadline_order(self):
        clock = EventClock()
        wakes = []

        def sleeper(ms):
            def body():
                yield ("sleep", ms)
                wakes.append((ms, clock.now_ms()))
            return body

        for ms in (300.0, 100.0, 200.0):
            clock.spawn(sleeper(ms), name=f"s{ms}")

        def main():
            yield ("sleep", 400.0)

        clock.run(main())
        assert wakes == [(100.0, 100.0), (200.0, 200.0), (300.0, 300.0)]

    def test_queue_get_timeout_is_simulated(self):
        clock = EventClock()
        q = clock.queue()

        def main():
            try:
                yield ("get", q, 3600.0)  # one simulated hour
            except queue.Empty:
                return clock.now_ms()
            raise AssertionError("get should have timed out")

        assert clock.run(main()) == pytest.approx(3600e3)

    def test_queue_put_wakes_blocked_actor(self):
        clock = EventClock()
        q = clock.queue()
        got = []

        def consumer():
            got.append((yield ("get", q, 60.0)))

        clock.spawn(consumer, name="consumer")

        def main():
            yield ("charge", 5.0)  # let the consumer park first
            q.put("payload")
            yield ("charge", 1.0)

        clock.run(main())
        assert got == ["payload"]
        assert clock.now_ms() < 60e3  # woken by the put, not the timeout

    def test_lock_contention_charges_waiters_for_the_hold(self):
        clock = EventClock()
        lane = clock.lock()
        spans = []

        def transfer():
            yield ("acquire", lane)
            t0 = clock.now_ms()
            yield ("charge", 100.0)
            spans.append((t0, clock.now_ms()))
            lane.release()

        for _ in range(3):
            clock.spawn(transfer, name="t")

        def main():
            yield ("sleep", 1000.0)

        clock.run(main())
        assert spans == [(0.0, 100.0), (100.0, 200.0), (200.0, 300.0)]

    def test_event_wait_timeout_and_set(self):
        clock = EventClock()
        ev = clock.event()

        def main():
            flag = yield ("wait", ev, 0.5)  # simulated 500 ms
            assert flag is False
            assert clock.now_ms() == pytest.approx(500.0)
            ev.set()
            flag = yield ("wait", ev, 0.5)
            assert flag is True
            return clock.now_ms()

        assert clock.run(main()) == pytest.approx(500.0)  # no extra wait

    def test_flush_applies_deferred_direct_charges(self):
        # Non-yieldable code (simulated_compute inside task fns) calls
        # clock.charge() directly: billed immediately, time advance
        # deferred until the frame's next ("flush",).
        clock = EventClock()

        def main():
            clock.charge(42.0)
            assert clock.charged_ms == 42.0
            assert clock.now_ms() == 0.0  # not yet advanced
            yield ("flush",)
            return clock.now_ms()

        assert clock.run(main()) == 42.0

    def test_external_thread_drives_effects_blockingly(self):
        # run_effects is the bridge for code running on a real OS thread
        # (the same generator protocol, mapped onto blocking waits).
        clock = EventClock()
        q = clock.queue()
        out = []

        def external():
            def gen():
                out.append((yield ("get", q, 5.0)))
            run_effects(clock, gen())

        t = threading.Thread(target=external)
        t.start()

        def main():
            yield ("charge", 1.0)
            q.put(42)

        clock.run(main())
        t.join(timeout=5.0)
        assert out == [42]

    def test_charge_outside_any_frame_bills_without_advancing(self):
        clock = EventClock()
        clock.charge(123.0)
        assert clock.charged_ms == 123.0
        assert clock.now_ms() == 0.0

    def test_charge_inside_actor_advances_time(self):
        clock = EventClock()
        with clock.actor():
            clock.charge(250.0)
            clock.charge(125.0)
            assert clock.now_ms() == 375.0
        assert clock.charged_ms == 375.0

    def test_unregistered_thread_blocks_on_a_real_timeout(self):
        # A thread that never registered waits in real seconds, and
        # simulated time stays where it was.
        clock = EventClock()
        q = clock.queue()
        t0 = time.perf_counter()
        with pytest.raises(queue.Empty):
            q.get(timeout=0.05)
        assert time.perf_counter() - t0 >= 0.05
        assert clock.now_ms() == 0.0
        out = []
        t = threading.Thread(target=lambda: out.append(q.get(timeout=5.0)))
        t.start()
        q.put(42)
        t.join(timeout=5.0)
        assert out == [42]


# ---------------------------------------------------------------------------
# Engine-level determinism
# ---------------------------------------------------------------------------


def _rich_config():
    """Virtual-mode engine exercising every stochastic knob: latency
    jitter, cold starts, fault injection with retry backoff."""
    return EngineConfig(
        cost=CostModel(invoke_sigma=0.3, warm_fraction=0.7, latency_seed=7),
        faults=FaultConfig(task_failure_prob=0.04, max_retries=2, seed=21,
                           retry_backoff_base_ms=1000.0),
    )


class TestDeterminism:
    def test_identical_runs_bit_identical(self):
        """Acceptance: two identical seeded virtual runs agree on
        results, wall_s, charged_ms, AND the full metrics trace."""
        reps = []
        for _ in range(2):
            dag = tree_reduction_dag(64, compute_ms=250.0,
                                     payload_bytes=1 << 16)
            reps.append(WukongEngine(_rich_config()).compute(dag))
        a, b = reps
        (ka, va), = a.results.items()
        (kb, vb), = b.results.items()
        assert ka == kb and va[0] == vb[0] == tree_reduction_expected(64)
        assert a.wall_s == b.wall_s
        assert a.charged_ms == b.charged_ms
        assert a.kv_stats == b.kv_stats
        assert a.executors_invoked == b.executors_invoked
        assert a.metrics == b.metrics  # same records, same ORDER

    def test_metrics_stamped_with_virtual_time(self):
        rep = WukongEngine().compute(tree_reduction_dag(16,
                                                        compute_ms=100.0))
        stamps = [m["at_ms"] for m in rep.metrics]
        assert stamps and all(s >= 0.0 for s in stamps)
        assert max(stamps) <= rep.wall_s * 1e3 + 1e-6
        # simulated compute is visible in the per-task breakdown
        executed = [m for m in rep.metrics if m.get("event") == "executed"]
        assert all(m["compute_ms"] == pytest.approx(100.0)
                   for m in executed)

    def test_latency_seed_changes_the_trace(self):
        def run(seed):
            cfg = EngineConfig(cost=CostModel(
                invoke_sigma=0.3, warm_fraction=0.5, latency_seed=seed))
            return WukongEngine(cfg).compute(
                tree_reduction_dag(32, compute_ms=50.0))

        assert run(1).charged_ms != run(2).charged_ms
        assert run(3).charged_ms == run(3).charged_ms

    def test_cold_starts_cost_more_than_warm_pool(self):
        def run(warm):
            cfg = EngineConfig(cost=CostModel(warm_fraction=warm))
            return WukongEngine(cfg).compute(tree_reduction_dag(32))

        assert run(0.0).charged_ms > run(1.0).charged_ms


# ---------------------------------------------------------------------------
# Scale: simulated seconds are free
# ---------------------------------------------------------------------------


class TestScale:
    def test_job_timeout_means_simulated_seconds(self):
        """A 10-simulated-minute timeout on a stuck job fires instantly
        in wall time: the clock jumps straight to the deadline."""
        cfg = EngineConfig(
            cost=CostModel(),
            job_timeout_s=600.0,
            # a task that "runs" 20 simulated minutes can never finish
            faults=FaultConfig(straggler_prob=1.0,
                               straggler_slowdown_ms=1200e3, seed=1),
        )
        t0 = time.perf_counter()
        with pytest.raises(JobError, match="timed out"):
            WukongEngine(cfg).compute(tree_reduction_dag(4))
        assert time.perf_counter() - t0 < 30.0

    def test_4096_leaf_tree_reduction_under_wall_budget(self):
        """Acceptance: a 4096-leaf TR (8191 tasks, ~7 simulated minutes)
        completes correctly within a wall-time budget — the DAG scale
        the seed's 512-thread real-time cap could never reach."""
        n = 8192  # 4096 leaf tasks
        dag = tree_reduction_dag(n, compute_ms=500.0)
        cfg = EngineConfig(max_concurrency=8192, job_timeout_s=3600.0)
        t0 = time.perf_counter()
        rep = WukongEngine(cfg).compute(dag)
        wall = time.perf_counter() - t0
        (_, v), = rep.results.items()
        assert v[0] == tree_reduction_expected(n)
        assert rep.tasks == n - 1
        assert rep.wall_s > 10.0       # minutes of simulated time...
        assert wall < 120.0            # ...in seconds of wall time

    @pytest.mark.slow
    def test_100k_task_tree_reduction_under_wall_budget(self):
        n = 131072  # 131071 tasks
        dag = tree_reduction_dag(n, compute_ms=1.0)
        cfg = EngineConfig(max_concurrency=n, job_timeout_s=1e6,
                           record_metrics=False)
        t0 = time.perf_counter()
        rep = WukongEngine(cfg).compute(dag)
        wall = time.perf_counter() - t0
        (_, v), = rep.results.items()
        assert v[0] == tree_reduction_expected(n)
        assert rep.tasks == n - 1
        assert rep.metrics == []  # record_metrics=False
        assert wall < 30.0


class TestRetryBackoff:
    def test_backoff_is_charged_not_slept(self):
        base = EngineConfig(faults=FaultConfig(
            task_failure_prob=0.04, max_retries=2, seed=21))
        slow = EngineConfig(faults=FaultConfig(
            task_failure_prob=0.04, max_retries=2, seed=21,
            retry_backoff_base_ms=60e3))  # Lambda-style ~1 min waits
        dag = tree_reduction_dag(32)
        r0 = WukongEngine(base).compute(tree_reduction_dag(32))
        t0 = time.perf_counter()
        r1 = WukongEngine(slow).compute(dag)
        assert time.perf_counter() - t0 < 30.0  # backoff cost no wall time
        (_, v), = r1.results.items()
        assert v[0] == tree_reduction_expected(32)
        assert r1.charged_ms - r0.charged_ms >= 60e3 - 1.0  # >= one backoff
