"""Dynamic DAGs: runtime graph expansion (repro.core.dag.DynamicDAG).

The tentpole property: a DAG that grows at runtime (a task returns an
``Expansion`` instead of a value) charges EXACTLY what the statically
pre-expanded equivalent graph charges — same results, same charged_ms,
same KV traffic. Plus the expansion
validation surface, iterate-until-converged chaining with the depth
cap, and the idempotent-replay path that makes duplicate execution of
an expanding task (crash resume) safe.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.apps import (
    dynamic_tree_reduction_dag,
    dynamic_tree_reduction_expected,
    static_tree_reduction_equivalent,
)
from repro.core import (
    EXPAND_BASE,
    CostModel,
    DynamicDAG,
    EngineConfig,
    Expansion,
    ExpansionError,
    Task,
    TaskRef,
    WukongEngine,
    expansion_base_key,
)

def _engine() -> WukongEngine:
    # schedule_ship_mbps=inf: expansion schedules are built after
    # dispatch, so static-schedule shipping is the one cost the dynamic
    # arm structurally cannot share with the pre-expanded equivalent.
    return WukongEngine(EngineConfig(
        cost=CostModel(schedule_ship_mbps=float("inf")),
        num_initial_invokers=4, num_proxy_invokers=4,
        max_concurrency=512))


def _dyn() -> DynamicDAG:
    return DynamicDAG([
        Task("src", lambda: np.array([1.0]), ()),
        Task("out", lambda x: x, (TaskRef("src"),)),
    ])


def _sub(final: str = "b") -> "tuple[Task, ...]":
    return (
        Task("a", lambda v: v, (TaskRef(EXPAND_BASE),)),
        Task("b", lambda v: v, (TaskRef("a"),)),
    )[: (2 if final == "b" else 1)]


# ---------------------------------------------------------------------------
# Construction-time / expansion-time validation
# ---------------------------------------------------------------------------


class TestValidation:
    @pytest.mark.parametrize("depth", [0, -1, 1.5, True, "8"])
    def test_bad_max_expansion_depth(self, depth):
        with pytest.raises(ValueError, match="max_expansion_depth"):
            DynamicDAG([Task("t", lambda: 1, ())],
                       max_expansion_depth=depth)

    def test_unknown_key(self):
        with pytest.raises(ExpansionError, match="unknown task"):
            _dyn().apply_expansion(
                "nope", Expansion(1.0, _sub(), "b"))

    def test_empty_expansion(self):
        with pytest.raises(ExpansionError, match="empty"):
            _dyn().apply_expansion("out", Expansion(1.0, (), "b"))

    def test_duplicate_keys(self):
        dup = (Task("a", lambda v: v, (TaskRef(EXPAND_BASE),)),
               Task("a", lambda v: v, (TaskRef(EXPAND_BASE),)))
        with pytest.raises(ExpansionError, match="duplicate keys"):
            _dyn().apply_expansion("out", Expansion(1.0, dup, "a"))

    def test_final_not_in_tasks(self):
        with pytest.raises(ExpansionError, match="final"):
            _dyn().apply_expansion("out", Expansion(1.0, _sub(), "zzz"))

    def test_key_collision_with_existing_task(self):
        clash = (Task("src", lambda v: v, (TaskRef(EXPAND_BASE),)),)
        with pytest.raises(ExpansionError, match="collide"):
            _dyn().apply_expansion("out", Expansion(1.0, clash, "src"))

    def test_external_dependency_rejected(self):
        leaky = (Task("a", lambda v, w: v,
                      (TaskRef(EXPAND_BASE), TaskRef("src"))),)
        with pytest.raises(ExpansionError, match="self-contained"):
            _dyn().apply_expansion("out", Expansion(1.0, leaky, "a"))

    def test_dependency_on_final_rejected(self):
        bad = (Task("a", lambda v: v, (TaskRef("b"),)),
               Task("b", lambda v: v, (TaskRef(EXPAND_BASE),)))
        with pytest.raises(ExpansionError,
                           match="depends on the final"):
            _dyn().apply_expansion("out", Expansion(1.0, bad, "b"))

    def test_orphan_task_rejected(self):
        orphan = (Task("a", lambda v: v, (TaskRef(EXPAND_BASE),)),
                  Task("b", lambda: 1.0, ()))
        with pytest.raises(ExpansionError, match="never be triggered"):
            _dyn().apply_expansion("out", Expansion(1.0, orphan, "a"))

    def test_no_base_consumer_rejected(self):
        # No task reads EXPAND_BASE: the subgraph has no entry point.
        lone = (Task("a", lambda v: v, (TaskRef("b"),)),
                Task("b", lambda v: v, (TaskRef("a"),)),
                Task("z", lambda v: v, (TaskRef("a"),)))
        with pytest.raises(ExpansionError, match="EXPAND_BASE"):
            _dyn().apply_expansion("out", Expansion(1.0, lone, "z"))

    def test_cycle_rejected(self):
        cyc = (Task("e", lambda v: v, (TaskRef(EXPAND_BASE),)),
               Task("a", lambda v, w: v, (TaskRef("e"), TaskRef("b"))),
               Task("b", lambda v: v, (TaskRef("a"),)),
               Task("f", lambda v: v, (TaskRef("b"),)))
        with pytest.raises(ExpansionError, match="cycle"):
            _dyn().apply_expansion("out", Expansion(1.0, cyc, "f"))

    def test_dag_factory_validation(self):
        with pytest.raises(ValueError, match="power of two"):
            dynamic_tree_reduction_dag(6)
        with pytest.raises(ValueError, match="power of two"):
            dynamic_tree_reduction_dag(2)


# ---------------------------------------------------------------------------
# Expansion mechanics: delta shape, chaining, depth cap, replay
# ---------------------------------------------------------------------------


class TestExpansionMechanics:
    def test_delta_shape(self):
        dag = _dyn()
        delta = dag.apply_expansion("out", Expansion(7.0, _sub(), "b"))
        assert delta.key == "out"
        assert delta.base_key == expansion_base_key("out", 0)
        assert delta.value == 7.0
        assert delta.new_keys == ("a",)  # final excluded
        assert delta.topo[0] == delta.base_key
        assert delta.topo[-1] == "out"  # final re-bound under key
        assert not delta.replayed
        assert dag.expansions_applied == 1
        # the re-bound graph stays acyclic and topo-sortable
        order = dag.topological_order()
        assert order.index(delta.base_key) < order.index("a") \
            < order.index("out")

    def test_identical_replay_is_idempotent(self):
        # A duplicate execution (crash resume re-running the expanding
        # task with identical inputs) re-produces the same value and the
        # same subgraph: deduped, the graph does not grow twice.
        dag = _dyn()
        first = dag.apply_expansion("out", Expansion(7.0, _sub(), "b"))
        again = dag.apply_expansion("out", Expansion(7.0, _sub(), "b"))
        assert again.replayed
        assert again.base_key == first.base_key
        assert again.new_keys == first.new_keys
        assert dag.expansions_applied == 1

    def test_new_value_same_keys_is_not_a_replay(self):
        # Same subgraph shape but a NEW value is the next round of an
        # iteration, not a replay — and with multi-task subgraphs the
        # non-final key names must be fresh, so this one collides.
        dag = _dyn()
        dag.apply_expansion("out", Expansion(7.0, _sub(), "b"))
        with pytest.raises(ExpansionError, match="collide"):
            dag.apply_expansion("out", Expansion(9.0, _sub(), "b"))

    def test_depth_cap(self):
        dag = DynamicDAG([
            Task("src", lambda: 1.0, ()),
            Task("out", lambda x: x, (TaskRef("src"),)),
        ], max_expansion_depth=2)
        for i in range(2):
            dag.apply_expansion("out", Expansion(
                1.0,
                (Task(f"t{i}", lambda v: v, (TaskRef(EXPAND_BASE),)),),
                f"t{i}"))
        with pytest.raises(ExpansionError, match="depth"):
            dag.apply_expansion("out", Expansion(
                1.0,
                (Task("t9", lambda v: v, (TaskRef(EXPAND_BASE),)),),
                "t9"))


# ---------------------------------------------------------------------------
# Engine-level: iterate-until-converged + charged parity (hypothesis)
# ---------------------------------------------------------------------------


def _countdown_dag(rounds: int, depth_cap: int = 16) -> DynamicDAG:
    """Each expansion's final decrements and re-expands until zero —
    the iterate-until-converged shape (rounds chained expansions)."""

    def step(v):
        v = np.asarray(v, dtype=float) - 1.0
        if v[0] <= 0.0:
            return v
        return Expansion(value=v,
                         tasks=(Task("next", step,
                                     (TaskRef(EXPAND_BASE),)),),
                         final="next")

    return DynamicDAG([
        Task("seed", lambda: np.array([float(rounds)]), ()),
        Task("iter", step, (TaskRef("seed"),)),
    ], max_expansion_depth=depth_cap)


class TestEngineIntegration:
    def test_iterate_until_converged(self):
        rep = _engine().compute(_countdown_dag(4))
        (_, v), = rep.results.items()
        assert v[0] == 0.0
        # 1 seed + the initial iter + 3 re-expanded finals
        assert rep.tasks == 5

    def test_depth_cap_surfaces_as_job_error(self):
        from repro.core import JobError
        with pytest.raises(JobError, match="depth"):
            _engine().compute(_countdown_dag(6, depth_cap=2))

    @settings(max_examples=5, deadline=None)
    @given(n=st.sampled_from([4, 8, 16, 32]),
           compute_ms=st.sampled_from([0.0, 3.0]))
    def test_dynamic_matches_static_equivalent(self, n, compute_ms):
        """The PR's core parity property: data-dependent fan-out priced
        bit-identically to the pre-expanded graph."""
        dyn = _engine().compute(
            dynamic_tree_reduction_dag(n, compute_ms=compute_ms))
        sta = _engine().compute(
            static_tree_reduction_equivalent(n, compute_ms=compute_ms))
        assert np.array_equal(np.asarray(dyn.results["reduce"]),
                              np.asarray(sta.results["reduce"]))
        assert dyn.results["reduce"][0] == dynamic_tree_reduction_expected(n)
        assert dyn.charged_ms == sta.charged_ms
        assert dyn.tasks == sta.tasks
        assert dyn.kv_stats == sta.kv_stats
