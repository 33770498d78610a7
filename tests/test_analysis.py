"""Tests for the determinism sanitizer and static analysis
(``repro.analysis``): every lint rule catches its seeded fixture
violation at the expected line, the real source tree is clean under the
shipped baseline, the unified dagcheck pass rejects seeded structural
corruption, and ``diff_traces`` pinpoints injected nondeterminism.
"""
import dataclasses
import random
from pathlib import Path

import pytest

from repro.analysis import (
    ConsistencyError,
    CycleError,
    ExpansionError,
    Tracer,
    check_compiled,
    check_expansion,
    check_fan_in_counters,
    check_schedule_set,
    diff_traces,
    lint_file,
    load_baseline,
    new_findings,
    verify_dag,
)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.dagcheck import fan_in_counter_id, toposort
from repro.analysis.divergence import TraceEvent
from repro.analysis.effects import lint_source, lint_tree
from repro.core.dag import DAG, DynamicDAG, Expansion, Task, TaskRef
from repro.core.optimize import compile_dag
from repro.core.schedule import generate_static_schedules
from repro.core.simclock import EventClock, VirtualClock

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
REPO = Path(__file__).parent.parent


def mark_line(name: str, mark: str) -> int:
    """1-indexed line of the ``MARK:<mark>`` sentinel in a fixture."""
    text = (FIXTURES / name).read_text().splitlines()
    for i, line in enumerate(text, 1):
        if f"MARK:{mark}" in line:
            return i
    raise AssertionError(f"no MARK:{mark} in {name}")


def rule_lines(name: str, rule: str) -> set:
    return {f.line for f in lint_file(FIXTURES / name, FIXTURES)
            if f.rule == rule}


# ---------------------------------------------------------------------------
# Lint rules, one seeded fixture violation each (file:line asserted)
# ---------------------------------------------------------------------------


def test_wallclock_rule_flags_each_call_form():
    lines = rule_lines("bad_wallclock.py", "REPRO001")
    for mark in ("time-time", "perf-counter", "datetime-now",
                 "from-import-monotonic"):
        assert mark_line("bad_wallclock.py", mark) in lines, mark


def test_wallclock_pragma_suppresses_site():
    lines = rule_lines("bad_wallclock.py", "REPRO001")
    assert mark_line("bad_wallclock.py", "pragma-ok") not in lines


def test_random_rule_flags_global_and_unseeded():
    lines = rule_lines("bad_random.py", "REPRO002")
    for mark in ("global-random", "from-import-shuffle", "unseeded-ctor"):
        assert mark_line("bad_random.py", mark) in lines, mark
    assert mark_line("bad_random.py", "seeded-ok") not in lines


def test_mutation_after_yield_rule():
    lines = rule_lines("bad_generator.py", "REPRO010")
    assert mark_line("bad_generator.py", "post-yield-mutation") in lines
    # not: pre-yield mutation, effect-lane-held mutation, or any
    # mutation in a frame-confined (lock-free) class
    for mark in ("pre-yield-ok", "lane-held-ok", "frame-local-ok"):
        assert mark_line("bad_generator.py", mark) not in lines, mark


def test_lock_across_yield_rule():
    lines = rule_lines("bad_generator.py", "REPRO011")
    assert lines == {mark_line("bad_generator.py", "lock-across-yield")}


def test_blocking_kv_in_generator_rule():
    lines = rule_lines("bad_generator.py", "REPRO012")
    assert lines == {mark_line("bad_generator.py", "blocking-kv")}


def test_task_clock_without_flush_rule():
    lines = rule_lines("bad_generator.py", "REPRO013")
    assert lines == {mark_line("bad_generator.py", "task-clock-no-flush")}


def test_key_hygiene_rules():
    assert mark_line("bad_keys.py", "namespace-literal") in \
        rule_lines("bad_keys.py", "REPRO020")
    assert rule_lines("bad_keys.py", "REPRO021") == \
        {mark_line("bad_keys.py", "builtin-hash")}
    assert mark_line("bad_keys.py", "crc32-ok") not in \
        rule_lines("bad_keys.py", "REPRO021")


def test_clean_actor_fixture_has_no_findings():
    assert lint_file(FIXTURES / "good_actor.py", FIXTURES) == []


def test_findings_carry_snippet_and_str():
    f = [x for x in lint_file(FIXTURES / "bad_keys.py", FIXTURES)
         if x.rule == "REPRO021"][0]
    assert "hash(key)" in f.snippet
    assert f"bad_keys.py:{f.line}" in str(f)


def test_substrate_file_is_exempt_from_wallclock_rule():
    src = "import time\n\ndef now() -> float:\n    return time.time()\n"
    assert any(f.rule == "REPRO001"
               for f in lint_source(src, "repro/core/other.py"))
    assert not any(f.rule == "REPRO001"
                   for f in lint_source(src, "repro/core/simclock.py"))


def test_jax_side_dirs_exempt_from_determinism_rules():
    src = "import time\nT0 = time.time()\nKEY = 'a::b'\n"
    findings = lint_source(src, "repro/runtime/train_loop.py")
    assert not any(f.rule == "REPRO001" for f in findings)
    # key hygiene still applies everywhere
    assert any(f.rule == "REPRO020" for f in findings)


def test_real_source_tree_clean_under_shipped_baseline():
    findings = lint_tree(REPO / "src")
    baseline = load_baseline(REPO / "analysis-baseline.json")
    fresh = new_findings(findings, baseline)
    assert fresh == [], "\n".join(str(f) for f in fresh)


def test_cli_gate_and_baseline_roundtrip(tmp_path, capsys):
    baseline = tmp_path / "baseline.json"
    argv = ["--check", str(FIXTURES), "--baseline", str(baseline)]
    assert analysis_main(argv) == 1  # seeded violations, empty baseline
    capsys.readouterr()
    assert analysis_main(argv + ["--write-baseline"]) == 0
    capsys.readouterr()
    assert analysis_main(argv) == 0  # grandfathered now
    capsys.readouterr()
    assert analysis_main(["--check", str(tmp_path / "nope")]) == 2
    assert analysis_main(["--explain"]) == 0


# ---------------------------------------------------------------------------
# Unified dagcheck pass
# ---------------------------------------------------------------------------


def _add(*xs):
    return sum(xs)


def _diamond() -> DAG:
    return DAG([
        Task("a", _add),
        Task("b", _add, (TaskRef("a"),)),
        Task("c", _add, (TaskRef("a"),)),
        Task("d", _add, (TaskRef("b"), TaskRef("c"))),
    ])


def test_verify_dag_accepts_built_graph():
    order = verify_dag(_diamond())
    assert set(order) == {"a", "b", "c", "d"}
    assert order.index("a") < order.index("d")


def test_verify_dag_catches_tampered_children():
    dag = _diamond()
    dag.children["a"].remove("b")  # corrupt the edge mirror
    with pytest.raises(ConsistencyError, match="dep edges missing"):
        verify_dag(dag)


def test_verify_dag_catches_tampered_leaves():
    dag = _diamond()
    dag.leaves = ("a", "b")
    with pytest.raises(ConsistencyError, match="leaves"):
        verify_dag(dag)


def test_toposort_raises_on_cycle():
    deps = {"x": ("y",), "y": ("x",)}
    children = {"x": ["y"], "y": ["x"]}
    with pytest.raises(CycleError, match="cycle"):
        toposort({"x": None, "y": None}, deps, children)


def test_check_expansion_rejects_collision_and_orphan():
    dag = DynamicDAG([Task("root", _add)])
    collide = Expansion(
        tasks=(Task("root", _add, (TaskRef("__expand_base__"),)),),
        final="root", value=1)
    with pytest.raises(ExpansionError, match="collide"):
        check_expansion(dag.tasks, "root", collide, "root/__base0__", 1, 8)
    orphan = Expansion(
        tasks=(Task("s0", _add, (TaskRef("__expand_base__"),)),
               Task("s1", _add)),
        final="s0", value=1)
    with pytest.raises(ExpansionError, match="never be triggered"):
        check_expansion(dag.tasks, "root", orphan, "root/__base0__", 1, 8)


def test_check_expansion_depth_cap():
    dag = DynamicDAG([Task("root", _add)])
    ok = Expansion(
        tasks=(Task("s0", _add, (TaskRef("__expand_base__"),)),),
        final="s0", value=1)
    with pytest.raises(ExpansionError, match="depth"):
        check_expansion(dag.tasks, "root", ok, "root/__base0__", 9, 8)


def test_fan_in_counter_check():
    dag = _diamond()
    good = {fan_in_counter_id("d"): 2}
    check_fan_in_counters(dag, good)
    with pytest.raises(ConsistencyError, match="width"):
        check_fan_in_counters(dag, {fan_in_counter_id("d"): 3})
    with pytest.raises(ConsistencyError, match="missing"):
        check_fan_in_counters(dag, {})
    with pytest.raises(ConsistencyError, match="non-fan-in"):
        check_fan_in_counters(
            dag, dict(good, **{fan_in_counter_id("b"): 1}))


def test_schedule_set_check_and_tampering():
    dag = _diamond()
    ss = generate_static_schedules(dag)
    check_schedule_set(ss)
    # drop an initial batch: the leaf is no longer covered exactly once
    tampered = dataclasses.replace(ss, batches=ss.batches[1:])
    with pytest.raises(ConsistencyError, match="covered by 0"):
        check_schedule_set(tampered)
    doubled = dataclasses.replace(ss, batches=ss.batches + ss.batches[:1])
    with pytest.raises(ConsistencyError, match="covered by 2"):
        check_schedule_set(doubled)


def test_compiled_dag_check_and_tampering():
    dag = _diamond()
    compiled = compile_dag(dag)  # runs check_compiled internally
    check_compiled(compiled)
    compiled.clusters["d"] = "not-a-task"
    with pytest.raises(ConsistencyError, match="non-task"):
        check_compiled(compiled)


def test_compiled_dag_leaf_batch_partition_check():
    compiled = compile_dag(_diamond())
    compiled.leaf_batches = compiled.leaf_batches + (("a",),)
    with pytest.raises(ConsistencyError, match="multiple leaf batches"):
        check_compiled(compiled)


@pytest.mark.parametrize("tamper,match", [
    (lambda c, k: c.fused.update({k: c.fused[k][:-1]}), "must end with"),
    (lambda c, k: c.fused.update(
        {"gemm-C-0-1": c.fused[k][:1] + c.fused["gemm-C-0-1"]}), "fused twice"),
    (lambda c, k: c.fused.update({k: ("gemm-A-0-0",) + c.fused[k]}),
     "still a task"),
])
def test_compiled_dag_fused_provenance_check(tamper, match):
    from repro.apps import gemm_dag

    compiled = compile_dag(gemm_dag(128, 64))  # inlined groups, then chains
    key = "gemm-C-0-0"
    assert compiled.fused[key][-2:] == ("gemm-S-0-0-0-0", key)
    check_compiled(compiled)
    tamper(compiled, key)
    with pytest.raises(ConsistencyError, match=match):
        check_compiled(compiled)


# ---------------------------------------------------------------------------
# Runtime determinism sanitizer (trace mode + diff_traces)
# ---------------------------------------------------------------------------


def _traced_run(clock_cls, seed: int) -> Tracer:
    """One run of a job whose effect order depends on ``seed`` —
    standing in for an actor calling the *unseeded* global shuffle,
    which draws a different order every run."""
    clock = clock_cls()
    clock.tracer = Tracer()

    def actor():
        charges = [1.0, 2.0, 3.0, 4.0]
        random.Random(seed).shuffle(charges)
        for ms in charges:
            yield ("charge", ms)
        yield ("flush",)
        return sum(charges)

    assert clock.run(actor()) == 10.0
    return clock.tracer


def test_identical_runs_produce_identical_traces():
    assert diff_traces(_traced_run(EventClock, 7),
                       _traced_run(EventClock, 7)) is None


def test_cross_substrate_traces_match():
    assert diff_traces(_traced_run(EventClock, 7),
                       _traced_run(VirtualClock, 7)) is None


def test_diff_pinpoints_first_divergent_event_and_actor():
    div = diff_traces(_traced_run(EventClock, 7),
                      _traced_run(EventClock, 8))
    assert div is not None
    # the shuffled charge order splits at the very first charge
    assert div.index == 0
    assert div.left.effect == "charge" and div.right.effect == "charge"
    assert div.left.charge != div.right.charge
    assert div.left.actor.startswith("root#")
    desc = div.describe()
    assert "diverge" in desc and "charge" in desc


def test_diff_reports_truncated_trace():
    a = _traced_run(EventClock, 7)
    div = diff_traces(a, a.events[:-1])
    assert div is not None and div.right is None
    assert div.index == len(a.events) - 1


def test_diff_by_actor_tolerates_interleaving():
    def ev(seq, actor, charge):
        return TraceEvent(seq=seq, actor=actor, effect="charge",
                          charge=charge, src="x.py:1")

    a = [ev(0, "a#0", 1.0), ev(1, "b#1", 9.0), ev(2, "a#0", 2.0)]
    b = [ev(0, "b#1", 9.0), ev(1, "a#0", 1.0), ev(2, "a#0", 2.0)]
    assert diff_traces(a, b) is not None  # global order differs...
    # ...but per-actor sequences are identical (actors paired by
    # first-appearance order: a's [a#0, b#1] vs b's [b#1, a#0] pairs
    # a#0 with b#1 — use matching spawn order for a clean comparison)
    b_spawn_ordered = [ev(0, "a#0", 1.0), ev(1, "a#0", 2.0),
                       ev(2, "b#1", 9.0)]
    assert diff_traces(a, b_spawn_ordered, by_actor=True) is None
    # a per-actor divergence is attributed to the right actor
    b_bad = [ev(0, "a#0", 1.0), ev(1, "a#0", 5.0), ev(2, "b#1", 9.0)]
    div = diff_traces(a, b_bad, by_actor=True)
    assert div is not None and div.actor == "a#0" and div.index == 1
