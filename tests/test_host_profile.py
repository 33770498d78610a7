"""Host-time profile of a WukongEngine job (simclock.HostProfile).

A 512^2 GEMM in 128^2 blocks runs once under a CPU profiler session and
once without. The profiled job's layers sum to its ``wukong/job`` span,
its frame steps match the clock's, its spans land on the profiler's
host plane nested inside the caller's own, and nothing the simulation
reports moves. Without a session nothing is recorded or built.
"""
import contextlib
import gc
import itertools
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

import repro.core.engine as engine_mod
import repro.core.simclock as simclock
from repro.apps import gemm_dag
from repro.core import CostModel, EngineConfig, OptimizeConfig, WukongEngine
from repro.core.engine import HOST_LAYERS, profiler_capturing
from repro.core.kvstore import HostTimedKVStore, ShardedKVStore
from repro.core.simclock import HOST_LOG

N, BLOCK = 512, 128
CALLER = "test/compute"


def _job():
    return gemm_dag(N, BLOCK, seed_a=3, seed_b=4)


def _session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _host_spans(trace_dir):
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats).get("job"))
                    for e in line.events
                    if e.name.startswith(("wukong/", CALLER)))
    return spans


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    engine = WukongEngine(EngineConfig(optimize=OptimizeConfig()))
    engine.compute(_job())  # block programs compiled outside the session
    clocks = []
    real = engine_mod.clock_for_scale

    def recording_clock(*args):
        clocks.append(real(*args))
        return clocks[-1]

    engine_mod.clock_for_scale = recording_clock
    try:
        off = engine.compute(_job())
        trace_dir = tmp_path_factory.mktemp("trace")
        _session(trace_dir)
        try:
            assert profiler_capturing()
            with jax.profiler.TraceAnnotation(CALLER):
                on = engine.compute(_job())
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        engine_mod.clock_for_scale = real
    return {"off": off, "on": on, "clock": clocks[-1],
            "spans": _host_spans(trace_dir)}


def test_layers_sum_to_the_job_span(runs):
    rec = runs["on"].host_profile
    assert set(rec.layers_ns) == set(HOST_LAYERS)
    assert all(ns >= 0 for ns in rec.layers_ns.values())
    assert sum(rec.layers_ns.values()) == rec.end_ns - rec.start_ns
    (traced,) = [e - s for name, s, e, _ in runs["spans"] if name == "wukong/job"]
    assert sum(rec.layers_ns.values()) == pytest.approx(traced, rel=0.02)
    for layer in ("compile", "schedule", "task_fn", "kv", "walk", "invoker", "loop"):
        assert rec.layers_ns[layer] > 0, layer
    assert rec in HOST_LOG.jobs


def test_counters_match_what_the_job_did(runs):
    rep = runs["on"]
    assert rep.host_profile.frame_steps == runs["clock"].switches > 0
    names = [s[0] for s in runs["spans"]]
    assert names.count("wukong/task_fn") == rep.fault_stats["task_attempts"] == rep.tasks


def test_spans_nest_on_the_host_plane_inside_the_callers(runs):
    spans = runs["spans"]
    job_id = runs["on"].host_profile.job
    (caller,) = [s for s in spans if s[0] == CALLER]
    by_name = {}
    for name, s, e, job in spans:
        if name.startswith("wukong/"):
            assert job == job_id, name
            by_name.setdefault(name, []).append((s, e))

    def inside(inner, outer):
        return outer[0] <= inner[0] <= inner[1] <= outer[1]

    (job,) = by_name["wukong/job"]
    (compile_,) = by_name["wukong/compile"]
    (walk,) = by_name["wukong/walk"]
    (schedule,) = by_name["wukong/schedule"]
    tasks = sorted(by_name["wukong/task_fn"])
    assert len(tasks) == runs["on"].tasks
    assert inside(job, caller[1:3])
    assert inside(compile_, job) and inside(walk, job)
    assert compile_[1] <= walk[0]
    assert inside(schedule, walk)
    assert all(inside(t, walk) for t in tasks)
    assert schedule[1] <= tasks[0][0]
    assert all(a[1] <= b[0] for a, b in zip(tasks, tasks[1:]))


def test_every_charged_store_operation_charges_the_kv_layer():
    ops = {name for name in dir(ShardedKVStore)
           if name.endswith("_g") and not name.startswith("_")}
    assert len(ops) >= 11
    for name in ops:
        timed = getattr(HostTimedKVStore, name)
        assert timed is not getattr(ShardedKVStore, name)
        assert timed.__wrapped__ is getattr(ShardedKVStore, name)


def test_profiling_moves_nothing_the_simulation_reports(runs):
    off, on = runs["off"], runs["on"]
    assert off.host_profile is None
    assert on.charged_ms == off.charged_ms
    assert on.wall_s == off.wall_s
    assert on.kv_stats == off.kv_stats
    assert on.metrics == off.metrics
    assert on.results.keys() == off.results.keys()


def test_collections_are_logged_only_while_a_session_captures(runs):
    before = len(HOST_LOG.gc_pauses)
    gc.collect()
    assert len(HOST_LOG.gc_pauses) == before
    start = runs["on"].host_profile.start_ns / 1e9
    assert any(t >= start and d >= 0 for t, d in HOST_LOG.gc_pauses)


class _Refused:
    """A TraceAnnotation that may not be built."""

    is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

    def __init__(self, *args, **kwargs):
        raise AssertionError("TraceAnnotation built with no session")


@pytest.mark.parametrize("substrate", ["event", "thread"])
def test_without_a_session_nothing_is_recorded_or_built(substrate, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    jobs = list(HOST_LOG.jobs)
    engine = WukongEngine(EngineConfig(cost=CostModel(substrate=substrate),
                                       optimize=OptimizeConfig()))
    rep = engine.compute(gemm_dag(256, 128, seed_a=1, seed_b=2))
    assert not profiler_capturing()
    assert rep.host_profile is None
    assert list(HOST_LOG.jobs) == jobs


def test_thread_substrate_is_not_profiled_during_a_session(tmp_path):
    engine = WukongEngine(EngineConfig(cost=CostModel(substrate="thread"),
                                       optimize=OptimizeConfig()))
    jobs = len(HOST_LOG.jobs)
    _session(tmp_path)
    try:
        rep = engine.compute(gemm_dag(256, 128, seed_a=1, seed_b=2))
    finally:
        jax.profiler.stop_trace()
    assert rep.host_profile is None
    assert len(HOST_LOG.jobs) == jobs


def _ticking_profile(monkeypatch, clock):
    """A profile on ``clock`` whose every host clock read is one tick."""
    ticks = itertools.count(1000)
    monkeypatch.setattr(simclock, "_clock_ns", lambda: next(ticks))
    return simclock.HostProfile(clock, 0, lambda name: contextlib.nullcontext(),
                                HOST_LAYERS, idle="loop", frames="walk",
                                task=("wukong/task_fn", "task_fn"))


def test_steps_charge_their_frame_and_the_loop_between_them(monkeypatch):
    """Each host clock read is one tick: two charges make three steps of
    the root frame, each bracketed by two reads, with the loop between."""
    clock = simclock.EventClock()
    prof = _ticking_profile(monkeypatch, clock)
    assert clock.host_profile is prof

    def root():
        yield ("charge", 1.0)
        yield ("charge", 1.0)

    clock.run(root())
    rec = prof.finish()
    assert (rec.start_ns, rec.end_ns) == (1000, 1007)
    assert rec.layers_ns == dict.fromkeys(HOST_LAYERS, 0) | {"walk": 3, "loop": 4}
    assert rec.frame_steps == 3
    assert clock.host_profile is None


def test_a_frame_charges_the_layer_its_spawn_names(monkeypatch):
    """A lane spawned with ``layer="invoker"`` charges its one step there;
    the root frame, spawned with none, charges the profile's ``walk``."""
    clock = simclock.EventClock()
    prof = _ticking_profile(monkeypatch, clock)

    def lane():
        return
        yield

    def root():
        clock.spawn(lane, name="anything", layer="invoker")
        yield ("charge", 1.0)

    clock.run(root())
    rec = prof.finish()
    assert rec.layers_ns["invoker"] == 1
    assert rec.layers_ns["walk"] == 2
    assert rec.frame_steps == 3
