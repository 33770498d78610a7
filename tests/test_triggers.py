"""Trigger bus: event-fired jobs (repro.core.triggers).

Covers the construction-time validation surface of ``TriggerRule`` /
``StreamConfig``, the durable rule + fire journals (journal-then-act,
replay dedupe across crash generations), windowed kv_write matching,
the pub/sub teardown guarantee behind the relay (``drop_namespace``
wakes blocked subscribers with ``PURGED``), and the orchestrator
integration: all four trigger sources firing real jobs, bit-identical
repeat runs, and exactly-once fires across a mid-stream dispatcher
crash."""
import pytest

from repro.core import (
    PURGED,
    EngineConfig,
    FaultConfig,
    JobOrchestrator,
    OrchestratorConfig,
    ShardedKVStore,
    StreamConfig,
    TenantSpec,
    TriggerBus,
    TriggerRule,
    WorkloadConfig,
    stream_arrivals,
)
from repro.core.kvstore import NAMESPACE_SEP
from repro.core.simclock import EventClock

_ACTION = {"app": "tree_reduction", "size": 8, "tenant": "tenant-a"}


# ---------------------------------------------------------------------------
# Construction-time validation (the FaultConfig.__post_init__ discipline)
# ---------------------------------------------------------------------------


class TestTriggerRuleValidation:
    @pytest.mark.parametrize("kwargs,msg", [
        (dict(rule_id="", source="external", action=_ACTION, event="e"),
         "rule_id"),
        (dict(rule_id="a#b", source="external", action=_ACTION,
              event="e"), "rule_id"),
        (dict(rule_id="r", source="webhook", action=_ACTION), "source"),
        (dict(rule_id="r", source="external", action="not-a-mapping",
              event="e"), "action"),
        (dict(rule_id="r", source="external", action={"app": "x"},
              event="e"), "action"),
        (dict(rule_id="r", source="timer", action=_ACTION,
              period_ms=-1.0, max_fires=1), "period_ms"),
        (dict(rule_id="r", source="kv_write", action=_ACTION,
              key_prefix="p", window_ms=-2.0), "window_ms"),
        (dict(rule_id="r", source="kv_write", action=_ACTION,
              key_prefix="p", slide_ms=-2.0), "slide_ms"),
        (dict(rule_id="r", source="external", action=_ACTION, event="e",
              max_fires=-1), "max_fires"),
        (dict(rule_id="r", source="kv_write", action=_ACTION,
              key_prefix="p", min_window_events=0), "min_window_events"),
        (dict(rule_id="r", source="job_completed", action=_ACTION,
              every_n=0), "every_n"),
        (dict(rule_id="r", source="timer", action=_ACTION,
              max_fires=1), "period_ms"),
        (dict(rule_id="r", source="timer", action=_ACTION,
              period_ms=10.0), "max_fires"),
        (dict(rule_id="r", source="kv_write", action=_ACTION),
         "key_prefix"),
        (dict(rule_id="r", source="kv_write", action=_ACTION,
              key_prefix="p", window_ms=10.0, slide_ms=20.0), "slide"),
        (dict(rule_id="r", source="external", action=_ACTION), "event"),
    ])
    def test_rejects(self, kwargs, msg):
        with pytest.raises(ValueError, match=msg):
            TriggerRule(**kwargs)

    def test_valid_rule_copies_action(self):
        action = dict(_ACTION)
        rule = TriggerRule("r", "external", action, event="go")
        action["app"] = "mutated"
        assert rule.action["app"] == "tree_reduction"


class TestStreamConfigValidation:
    @pytest.mark.parametrize("kwargs,msg", [
        (dict(n_events=0), "n_events"),
        (dict(rate_per_s=0.0), "rate_per_s"),
        (dict(rate_per_s=-5.0), "rate_per_s"),
        (dict(payload_bytes=-1), "payload_bytes"),
        (dict(namespace=""), "namespace"),
        (dict(namespace=f"a{NAMESPACE_SEP}b"), "namespace"),
        (dict(key_prefix=""), "key_prefix"),
    ])
    def test_rejects(self, kwargs, msg):
        with pytest.raises(ValueError, match=msg):
            StreamConfig(**kwargs)

    def test_arrivals_deterministic_and_monotonic(self):
        cfg = StreamConfig(n_events=64, rate_per_s=100.0, seed=5)
        a, b = stream_arrivals(cfg), stream_arrivals(cfg)
        assert a == b
        assert all(x < y for x, y in zip(a, a[1:]))
        assert stream_arrivals(
            StreamConfig(n_events=64, rate_per_s=100.0, seed=6)) != a

    def test_store_prefix(self):
        cfg = StreamConfig(namespace="s", key_prefix="ev/")
        assert cfg.store_prefix == f"s{NAMESPACE_SEP}ev/"


# ---------------------------------------------------------------------------
# Bus unit tests: durable journals, dedupe, windowed matching
# ---------------------------------------------------------------------------


def _bus(id_base: int = 500):
    clock = EventClock()
    kv = ShardedKVStore(n_shards=4, clock=clock)
    return clock, kv, TriggerBus(kv, clock, id_base=id_base)


def _ext_event(name: str, ekey: str, at_ms: float = 0.0) -> dict:
    return {"source": "external", "name": name, "ekey": ekey,
            "payload": None, "at_ms": at_ms}


class TestBusJournals:
    def test_fire_journal_dedupes_and_replays(self):
        clock, kv, bus = _bus()
        rule = TriggerRule("r", "external", _ACTION, event="go")

        def main():
            yield from bus.add_rule_g(rule)
            with pytest.raises(ValueError, match="duplicate"):
                next(bus.add_rule_g(rule))
            (due,) = bus.match(_ext_event("go", "k1"))
            spec = yield from bus.fire_g(due, 1.0)
            assert spec["job_id"] == 500
            assert spec["app"] == "tree_reduction"
            assert spec["arrival_ms"] == 1.0
            # same fire key again: suppressed, not re-journaled
            (due2,) = bus.match(_ext_event("go", "k1", at_ms=9.0))
            assert (yield from bus.fire_g(due2, 9.0)) is None
            # a different dedup key is a genuine new fire
            (due3,) = bus.match(_ext_event("go", "k2", at_ms=9.0))
            spec3 = yield from bus.fire_g(due3, 9.0)
            assert spec3["job_id"] == 501

        clock.run(main())
        assert [r["fire_key"] for r in bus.fired_records()] \
            == ["r#k1", "r#k2"]

        # A fresh bus over the same store (the recovery path) folds the
        # journals back: same rules, same fires, same dedupe, and job
        # ids continue after the highest journaled one.
        clock2 = EventClock()
        bus2 = TriggerBus(kv, clock2, id_base=500)

        def recover():
            n = yield from bus2.replay_g()
            assert n == 3  # 1 rule + 2 fires
            (due,) = bus2.match(_ext_event("go", "k1"))
            assert (yield from bus2.fire_g(due, 0.0)) is None
            (due,) = bus2.match(_ext_event("go", "k3"))
            spec = yield from bus2.fire_g(due, 0.0)
            assert spec["job_id"] == 502

        clock2.run(recover())
        assert set(bus2.rules) == {"r"}
        assert len(bus2.fired_records()) == 3


class TestWindowedMatching:
    def _rule(self, **kw):
        kw.setdefault("window_ms", 100.0)
        return TriggerRule("w", "kv_write", _ACTION, key_prefix="s::ev/",
                           **kw)

    def _ev(self, key: str, at_ms: float) -> dict:
        return {"source": "kv_write", "key": key, "nbytes": 1,
                "at_ms": at_ms}

    def test_tumbling_close_on_watermark(self):
        _, _, bus = _bus()
        bus.rules["w"] = self._rule()
        # two events in window 0; nothing due until the watermark
        # (an event in a later window) passes the window end
        assert bus.match(self._ev("s::ev/000000@10.000", 10.0)) == []
        assert bus.match(self._ev("s::ev/000001@60.000", 60.0)) == []
        (due,) = bus.match(self._ev("s::ev/000002@150.000", 150.0))
        assert due["fire_key"] == "w#w0"
        assert due["event_times"] == [10.0, 60.0]

    def test_duplicate_write_delivery_ignored(self):
        _, _, bus = _bus()
        bus.rules["w"] = self._rule()
        key = "s::ev/000000@10.000"
        assert bus.match(self._ev(key, 10.0)) == []
        # crash-replay overlap: same durable key re-delivered
        assert bus.match(self._ev(key, 11.0)) == []
        (due,) = bus.match(self._ev("s::ev/000001@130.000", 130.0))
        assert due["event_times"] == [10.0]

    def test_min_window_events_suppresses_small_windows(self):
        _, _, bus = _bus()
        bus.rules["w"] = self._rule(min_window_events=2)
        assert bus.match(self._ev("s::ev/000000@10.000", 10.0)) == []
        # window 0 has 1 event < 2: closed silently, never fires
        assert bus.match(self._ev("s::ev/000001@150.000", 150.0)) == []
        assert bus.match(self._ev("s::ev/000002@160.000", 160.0)) == []
        (due,) = bus.match(self._ev("s::ev/000003@260.000", 260.0))
        assert due["fire_key"] == "w#w1"

    def test_flush_closes_open_windows(self):
        _, _, bus = _bus()
        bus.rules["w"] = self._rule()
        assert bus.match(self._ev("s::ev/000000@10.000", 10.0)) == []
        (due,) = bus.flush()
        assert due["fire_key"] == "w#w0"

    def test_sliding_windows_overlap(self):
        _, _, bus = _bus()
        bus.rules["w"] = self._rule(window_ms=100.0, slide_ms=50.0)
        # one event at 60 ms belongs to windows [0,100) and [50,150)
        assert bus.match(self._ev("s::ev/000000@60.000", 60.0)) == []
        dues = bus.match(self._ev("s::ev/000001@400.000", 400.0))
        assert [d["fire_key"] for d in dues] == ["w#w0", "w#w1"]
        assert all(d["event_times"] == [60.0] for d in dues)


# ---------------------------------------------------------------------------
# Pub/sub teardown behind the relay (drop_namespace wakes subscribers)
# ---------------------------------------------------------------------------


class TestPubSubTeardown:
    def test_drop_namespace_wakes_blocked_subscriber(self):
        clock = EventClock()
        kv = ShardedKVStore(n_shards=2, clock=clock)
        ns = kv.namespace("__triggers__")
        sub = ns.subscribe("events")
        woke = []

        def blocked():
            msg = yield ("get", sub, None)
            woke.append(msg)

        def main():
            yield ("charge", 1.0)
            kv.drop_namespace("__triggers__")
            yield ("flush",)

        clock.spawn(blocked, name="blocked")
        clock.run(main())
        assert woke == [PURGED]
        assert kv.subscriber_count(prefix="__triggers__") == 0


# ---------------------------------------------------------------------------
# Orchestrator integration: event-fired jobs end to end
# ---------------------------------------------------------------------------

_TENANTS = (TenantSpec("tenant-a"), TenantSpec("tenant-b"))


def _orch_config(crash_at: "int | None" = None) -> OrchestratorConfig:
    stream = StreamConfig(n_events=40, rate_per_s=40.0, seed=3,
                          flush_event="eos")
    faults = FaultConfig()
    if crash_at is not None:
        faults = FaultConfig(orchestrator_crash_point="dispatch",
                             orchestrator_crash_at=crash_at)
    return OrchestratorConfig(
        engine=EngineConfig(num_initial_invokers=4, num_proxy_invokers=4,
                            max_concurrency=512),
        workload=WorkloadConfig(n_jobs=2, tenants=_TENANTS, seed=1),
        max_concurrent_jobs=8,
        triggers=(
            TriggerRule("window", "kv_write", _ACTION,
                        key_prefix=stream.store_prefix, window_ms=250.0),
            TriggerRule("tick", "timer",
                        {"app": "tree_reduction", "size": 8,
                         "tenant": "tenant-b"},
                        period_ms=700.0, max_fires=2),
            TriggerRule("ckpt", "job_completed",
                        {"app": "dynamic_tree", "size": 8,
                         "tenant": "tenant-b"},
                        job_app="tree_reduction", every_n=4),
            TriggerRule("flush", "external", _ACTION, event="eos",
                        flush_windows=True),
        ),
        stream=stream,
        faults=faults,
    )


def _fire_summary(orch) -> "tuple[tuple, ...]":
    bus = orch.last_substrate.trigger_bus
    return tuple((r["fire_key"], r["source"], r["job_id"])
                 for r in bus.fired_records())


class TestOrchestratorStreaming:
    def test_all_four_sources_fire_jobs(self):
        orch = JobOrchestrator(_orch_config())
        rep = orch.run()
        assert rep.completed == rep.jobs and rep.failed == 0
        assert rep.jobs > 2  # trigger-fired jobs beyond the static two
        by_source = orch.last_substrate.trigger_bus.report().fires
        for source in ("timer", "kv_write", "job_completed", "external"):
            assert by_source.get(source, 0) >= 1, (source, by_source)
        # trigger-fired jobs carry bus-assigned ids above id_base
        trig_jobs = [r for r in rep.job_records
                     if r["job_id"] >= 1_000_000]
        assert len(trig_jobs) == rep.jobs - 2

    def test_repeat_runs_bit_identical(self):
        a = JobOrchestrator(_orch_config())
        b = JobOrchestrator(_orch_config())
        ra, rb = a.run(), b.run()
        assert ra.makespan_s == rb.makespan_s
        assert ra.billed_usd_total == rb.billed_usd_total
        assert _fire_summary(a) == _fire_summary(b)
        sa = a.last_substrate.trigger_bus.report(n_events=40)
        sb = b.last_substrate.trigger_bus.report(n_events=40)
        assert sa == sb

    def test_crash_mid_stream_recovers_exactly_once(self):
        base = JobOrchestrator(_orch_config())
        base_rep = base.run()
        crashed = JobOrchestrator(_orch_config(crash_at=5))
        rep = crashed.run_with_recovery()
        assert rep.crashes >= 1
        assert rep.completed == rep.jobs and rep.failed == 0
        # exactly-once: the journaled fire-key set matches the
        # uncrashed baseline (no lost window, no duplicate fire). Job
        # *ids* are allocated in event-arrival order, which legitimately
        # differs across crash generations — only uniqueness holds.
        assert [(k, s) for k, s, _ in _fire_summary(crashed)] \
            == [(k, s) for k, s, _ in _fire_summary(base)]
        ids = [r["job_id"] for r in rep.job_records]
        assert len(ids) == len(set(ids))
        assert rep.jobs == base_rep.jobs
