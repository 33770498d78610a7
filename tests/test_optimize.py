"""DAG compiler passes: correctness equivalence + pass-specific invariants.

The central property: an optimized DAG computes exactly what a sequential
topological evaluation of the ORIGINAL graph computes, on every engine and
under every pass combination. Pass-specific invariants: fusion never
crosses a fan-in/fan-out boundary, clustering strictly reduces KV ``set``
counts, coalescing strictly reduces executor invocations.
"""
import functools
import itertools
import operator
import random

import jax
import numpy as np
import pytest

from repro.core import (
    ALL_PASSES,
    NO_PASSES,
    CompiledDAG,
    EngineConfig,
    FaultConfig,
    GraphBuilder,
    OptimizeConfig,
    ParallelInvokerEngine,
    PubSubEngine,
    ServerfulConfig,
    ServerfulEngine,
    StrawmanEngine,
    WukongEngine,
    compile_dag,
)
from repro.core.dag import TaskRef
from repro.core.optimize import (
    coalesce_leaves,
    compute_clusters,
    find_chains,
    find_producer_groups,
    fuse_linear_chains,
    fusible_edges,
)


def seq_eval(dag):
    vals = {}
    for k in dag.topological_order():
        t = dag.tasks[k]
        args = [vals[a.key] if isinstance(a, TaskRef) else a for a in t.args]
        kwargs = {kk: vals[v.key] if isinstance(v, TaskRef) else v
                  for kk, v in t.kwargs.items()}
        vals[k] = t.fn(*args, **kwargs)
    return {k: vals[k] for k in dag.roots}


# -- DAG zoo ---------------------------------------------------------------


def chain_dag(n=20):
    """A pure linear chain (all interior edges fusible)."""
    g = GraphBuilder()
    cur = g.add(lambda: 1, name="start")
    for i in range(n):
        cur = g.add(lambda x: x + 1, cur, name=f"c{i}")
    return g.build()


def chained_fanin_dag(links=8):
    """A chain of fan-in diamonds: x_i = h(f(x_{i-1}), g(x_{i-1})).

    Every link has a width-2 fan-out followed by a width-2 fan-in, so no
    edge is fusible — isolating the clustering pass's delayed I/O.
    """
    g = GraphBuilder()
    cur = g.add(lambda: 1, name="x0")
    for i in range(links):
        a = g.add(lambda x: x + 1, cur, name=f"a{i}")
        b = g.add(lambda x: x * 2, cur, name=f"b{i}")
        cur = g.add(operator.add, a, b, name=f"x{i + 1}")
    return g.build()


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


def mixed_dag():
    """Chains + fan-outs + fan-ins + a wide sibling layer in one graph."""
    g = GraphBuilder()
    src = g.add(lambda: 2, name="src")
    pre = g.add(lambda x: x + 3, src, name="pre")      # fusible src->pre
    outs = []
    for i in range(12):
        h = g.add(lambda x, i=i: x * i, pre, name=f"h{i}")
        t = g.add(lambda x: x - 1, h, name=f"t{i}")    # fusible h->t
        outs.append(t)
    mid = g.add(lambda *xs: sum(xs), *outs, name="mid")
    g.add(lambda x: x % 97, mid, name="root")          # fusible mid->root
    return g.build()


ENGINES = [
    ("wukong", lambda o: WukongEngine(EngineConfig(optimize=o))),
    ("strawman", lambda o: StrawmanEngine(optimize=o)),
    ("pubsub", lambda o: PubSubEngine(optimize=o)),
    ("parallel_invoker", lambda o: ParallelInvokerEngine(optimize=o)),
    ("serverful",
     lambda o: ServerfulEngine(ServerfulConfig(optimize=o))),
]

PASS_COMBOS = [
    OptimizeConfig(fuse_chains=f, cluster_tasks=c, coalesce_fanouts=co)
    for f, c, co in itertools.product([False, True], repeat=3)
]


# -- equivalence: optimized == sequential, on every engine ------------------


@pytest.mark.parametrize("name,factory", ENGINES)
def test_all_engines_all_passes_tree(name, factory):
    want = seq_eval(tree_dag(32))
    assert factory(ALL_PASSES).compute(tree_dag(32)).results == want


@pytest.mark.parametrize("name,factory", ENGINES)
def test_all_engines_all_passes_mixed(name, factory):
    want = seq_eval(mixed_dag())
    assert factory(ALL_PASSES).compute(mixed_dag()).results == want


@pytest.mark.parametrize("combo", PASS_COMBOS,
                         ids=lambda c: f"fuse{int(c.fuse_chains)}-"
                                       f"clus{int(c.cluster_tasks)}-"
                                       f"coal{int(c.coalesce_fanouts)}")
def test_wukong_every_pass_combo_random_dags(combo):
    for seed in (3, 17, 42):
        dag = random_dag(seed, 45)
        want = seq_eval(dag)
        got = WukongEngine(
            EngineConfig(optimize=combo)).compute(random_dag(seed, 45))
        assert got.results == want


def test_chain_and_fanin_shapes_every_combo():
    for build in (chain_dag, chained_fanin_dag):
        want = seq_eval(build())
        for combo in PASS_COMBOS:
            rep = WukongEngine(EngineConfig(optimize=combo)).compute(build())
            assert rep.results == want, combo


def test_prebuilt_compiled_dag_equivalent_to_engine_config():
    g = GraphBuilder()
    cur = g.add(lambda: 5, name="s")
    for i in range(6):
        cur = g.add(lambda x: x * 2, cur, name=f"d{i}")
    via_build = WukongEngine().compute(g.build(optimize=True))
    via_config = WukongEngine(
        EngineConfig(optimize=ALL_PASSES)).compute(g.build())
    assert via_build.results == via_config.results == {"d5": 5 * 64}


# -- pass invariants: fusion ------------------------------------------------


def test_fusion_collapses_pure_chain_to_one_task():
    dag = chain_dag(20)
    compiled = compile_dag(dag, OptimizeConfig(
        cluster_tasks=False, coalesce_fanouts=False))
    assert isinstance(compiled, CompiledDAG)
    assert len(compiled) == 1
    assert compiled.roots == dag.roots
    assert compiled.fused["c19"][0] == "start"


def test_fusion_never_crosses_fanin_fanout_boundary():
    for build in (mixed_dag, lambda: random_dag(11, 60), chained_fanin_dag):
        dag = build()
        for chain in find_chains(dag):
            for u, v in zip(chain, chain[1:]):
                assert dag.fan_out_degree(u) == 1, (u, v)
                assert dag.fan_in_degree(v) == 1, (u, v)


def test_fusion_no_op_on_tree():
    # every tree edge targets a width-2 fan-in: nothing may fuse
    assert fusible_edges(tree_dag(16)) == set()


def test_fusion_respects_max_len():
    dag = chain_dag(20)  # 21 nodes
    _, provenance = fuse_linear_chains(dag, max_len=4)
    assert all(len(keys) <= 4 for keys in provenance.values())
    compiled = compile_dag(dag, OptimizeConfig(
        max_fusion_len=4, cluster_tasks=False, coalesce_fanouts=False))
    assert len(compiled) == 6  # ceil(21 / 4) segments
    rep = WukongEngine().compute(compiled)
    assert rep.results == seq_eval(dag)


def test_fused_task_preserves_kwargs_and_literals():
    g = GraphBuilder()
    a = g.add(lambda base, bump=0: base + bump, 10, bump=5, name="a")
    g.add(lambda x, scale=1: x * scale, a, scale=3, name="b")
    dag = g.build()
    rep = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(dag)
    assert rep.results == {"b": 45}


# -- pass invariants: producer inlining ------------------------------------

INLINE_ONLY = OptimizeConfig(fuse_chains=False, cluster_tasks=False,
                             coalesce_fanouts=False)
NO_INLINE = OptimizeConfig(inline_producers=False)


@jax.jit
def _neg(x):
    return -x


@jax.jit
def _twice(x):
    return 2.0 * x


@jax.jit
def _sub(x, y):
    return x - y


def _producer_dag(case):
    """src -> p -> c, with ``p`` built as ``case`` says; ``c`` is a jitted
    function of task outputs, so it roots a group whenever ``p`` may join."""
    from repro.apps.costing import flop_costed

    g = GraphBuilder()
    src = g.add(lambda: np.arange(4.0, dtype=np.float32), name="src")
    if case == "exclusive_jitted":
        p = g.add(_neg, src, name="p")
    elif case == "two_consumers":
        p = g.add(_neg, src, name="p")
        g.add(_twice, p, name="other")
    elif case == "costed_wrapper":
        p = g.add(flop_costed(_neg, 4.0, ms_per_flop=0.5), src, name="p")
    elif case == "partial":
        p = g.add(functools.partial(_sub, y=1.0), src, name="p")
    elif case == "numpy_body":
        p = g.add(np.negative, src, name="p")
    elif case == "literal_arg":
        p = g.add(_sub, src, 1.0, name="p")
    g.add(_twice, p, name="c")
    return g.build()


@pytest.mark.parametrize("case,absorbed", [
    ("exclusive_jitted", True),
    ("two_consumers", False),
    ("costed_wrapper", False),
    ("partial", False),
    ("numpy_body", False),
    ("literal_arg", False),
])
def test_inlining_absorbs_only_exclusive_jitted_producers(case, absorbed):
    dag = _producer_dag(case)
    compiled = compile_dag(dag, INLINE_ONLY)
    assert ("p" not in compiled.tasks) is absorbed
    assert (compiled.fused.get("c") == ("p", "c")) is absorbed
    assert compiled.deps["c"] == (("src",) if absorbed else ("p",))
    want = seq_eval(dag)
    got = WukongEngine().compute(compiled).results
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_inlining_caps_a_group_at_max_fusion_len():
    g = GraphBuilder()
    cur = g.add(lambda: np.float32(1.0), name="src")
    for i in range(10):
        cur = g.add(_twice, cur, name=f"j{i}")
    dag = g.build()
    groups = find_producer_groups(dag, max_len=4)
    assert [len(grp) for grp in groups] == [4, 4, 2]
    compiled = compile_dag(dag, OptimizeConfig(
        max_fusion_len=4, fuse_chains=False, cluster_tasks=False,
        coalesce_fanouts=False))
    assert len(compiled) == 1 + 3  # src and three groups
    assert all(len(keys) <= 4 for keys in compiled.fused.values())
    assert WukongEngine().compute(compiled).results == {"j9": 1024.0}


def test_inlined_programs_cache_is_bounded_for_per_call_closures():
    from repro.core.optimize import _group_program

    def dag_with_fresh_closures():
        g = GraphBuilder()
        src = g.add(lambda: np.float32(1.0), name="src")
        p = g.add(jax.jit(lambda x: x + 1), src, name="p")
        g.add(jax.jit(lambda x: x * 2), p, name="c")
        return g.build()

    cap = _group_program.cache_info().maxsize
    for _ in range(cap + 8):
        compile_dag(dag_with_fresh_closures(), INLINE_ONLY)
    assert _group_program.cache_info().currsize <= cap


@pytest.mark.parametrize("n,block,config,tasks", [
    (512, 64, ALL_PASSES, 192),
    (128, 64, ALL_PASSES, 12),
    (512, 64, NO_INLINE, 1088),
    (128, 64, NO_INLINE, 20),
])
def test_inlining_gemm_task_counts(n, block, config, tasks):
    from repro.apps import gemm_dag

    compiled = compile_dag(gemm_dag(n, block), config)
    assert len(compiled) == tasks
    inline = [s for s in compiled.pass_stats if s.name == "inline_producers"]
    if config.inline_producers:
        b = n // block
        (row,) = inline
        assert row.detail == (f"{b * b} programs formed, "
                              f"{b * b * (2 * b - 2)} tasks absorbed")
    else:
        assert inline == []


@pytest.mark.parametrize("n", [512, 128])
def test_inlined_gemm_matches_unfused_and_traces_once(n, monkeypatch):
    from repro.apps import gemm_dag
    from repro.core import optimize

    traces = []
    evaluate = optimize._evaluate

    def counting(fns, wiring, inputs, wrap=lambda v: v):
        if wrap is jax.lax.optimization_barrier:  # the program's body
            traces.append(len(fns))
        return evaluate(fns, wiring, inputs, wrap)

    monkeypatch.setattr(optimize, "_evaluate", counting)

    def run(config, seed_a, seed_b):
        dag = gemm_dag(n, 64, seed_a=seed_a, seed_b=seed_b)
        return WukongEngine(EngineConfig(optimize=config)).compute(dag).results

    seeds = [(11, 12), (13, 14)]
    traced = []
    for sa, sb in seeds:
        on = run(ALL_PASSES, sa, sb)
        traced.append(len(traces))
        off = run(NO_INLINE, sa, sb)
        assert on.keys() == off.keys() and len(on) == (n // 64) ** 2
        for k in on:
            np.testing.assert_allclose(np.asarray(on[k]), np.asarray(off[k]),
                                       rtol=1e-5, atol=1e-5)
    # The second job, on other seeds, traced nothing: every output block of
    # both jobs ran one program, compiled once. The root aliases'
    # producers are the groups' tasks.
    assert traced[1] == traced[0] <= 1
    programs = set()
    for sa, sb in seeds:
        dag = compile_dag(gemm_dag(n, 64, seed_a=sa, seed_b=sb), INLINE_ONLY)
        programs |= {dag.tasks[dag.deps[r][0]].fn.program for r in dag.roots}
    (program,) = programs
    assert program._cache_size() == 1


@pytest.mark.parametrize("limit,one_program", [
    (float("inf"), True),
    (0.0, False),
])
def test_inlined_group_runs_one_program_below_the_flops_limit(
        limit, one_program, monkeypatch):
    from repro.apps import gemm_dag
    from repro.core import optimize

    member_calls = []
    evaluate = optimize._evaluate

    def counting(fns, wiring, inputs, wrap=lambda v: v):
        if wrap is not jax.lax.optimization_barrier:  # member by member
            member_calls.append(len(fns))
        return evaluate(fns, wiring, inputs, wrap)

    monkeypatch.setattr(optimize, "_evaluate", counting)
    monkeypatch.setattr(optimize, "ONE_PROGRAM_MAX_FLOPS", limit)
    dag = gemm_dag(128, 64, seed_a=21, seed_b=22)
    on = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(dag)
    off = WukongEngine(EngineConfig(optimize=NO_INLINE)).compute(
        gemm_dag(128, 64, seed_a=21, seed_b=22))
    assert on.tasks == 12 and off.tasks == 20
    assert member_calls == ([] if one_program else [3] * 4)
    for k in off.results:
        np.testing.assert_allclose(np.asarray(on.results[k]),
                                   np.asarray(off.results[k]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("grid,block,one_program", [
    (2, 64, True),
    (8, 1024, True),     # gemm_8192.b1024: 1.15e9 operations a member
    (2, 4096, False),    # gemm_8192.b4096: 9.16e10 operations a member
])
def test_flops_per_member_of_gemm_groups(grid, block, one_program):
    import jax.numpy as jnp

    from repro.apps import gemm_dag
    from repro.core.optimize import ONE_PROGRAM_MAX_FLOPS, _flops_per_member

    dag = compile_dag(gemm_dag(64 * grid, 64), INLINE_ONLY)
    group = dag.tasks[dag.deps["gemm-C-0-0"][0]].fn
    leaves = ((block, block), jnp.dtype(jnp.float32))
    tree = jax.tree_util.tree_structure((0,) * (2 * grid))
    flops = _flops_per_member(group.fns, group.wiring, tree,
                              (leaves,) * (2 * grid))
    members = 2 * grid - 1
    assert flops == (grid * 2 * block ** 3 + (grid - 1) * block ** 2) / members
    assert (flops < ONE_PROGRAM_MAX_FLOPS) is one_program


def test_inlined_tsqr_matches_expected():
    from repro.apps.svd import tsqr_singular_values_expected, tsqr_svd_dag

    compiled = compile_dag(tsqr_svd_dag(1024, 32, 8), ALL_PASSES)
    assert compiled.tasks["svd1-R3-0"].fn.__name__.startswith("inlined_")
    s = WukongEngine().compute(compiled).results["svd1-S"]
    np.testing.assert_allclose(np.asarray(s),
                               tsqr_singular_values_expected(1024, 32, 8),
                               rtol=1e-4)


def _bypass_dags():
    from repro.apps import gemm_dag
    from repro.runtime.orchestrator import build_training_workflow

    train, _, _ = build_training_workflow(
        n_steps=4, step_fn=lambda st, b: (st + b, {"loss": st}),
        init_fn=lambda: 0.0, data_fn=lambda i: float(i + 1))
    return {"costed_gemm": gemm_dag(128, 64, ms_per_flop=1e-6),
            "training_workflow": train}


@pytest.mark.parametrize("name", ["costed_gemm", "training_workflow"])
def test_inlining_bypasses_costed_and_closure_bodies(name):
    on = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(
        _bypass_dags()[name])
    off = WukongEngine(EngineConfig(optimize=NO_INLINE)).compute(
        _bypass_dags()[name])
    assert on.optimizer[0].name == "inline_producers"
    assert on.optimizer[0].after_tasks == on.optimizer[0].before_tasks
    assert on.tasks == off.tasks
    assert on.charged_ms == off.charged_ms
    assert on.kv_stats == off.kv_stats
    assert on.results.keys() == off.results.keys()


def test_training_workflow_runs_without_the_compiler():
    from repro.runtime.orchestrator import run_training_workflow
    from repro.runtime.orchestrator import build_training_workflow

    dag, final, metrics = build_training_workflow(
        n_steps=3, step_fn=lambda st, b: (st + b, {}), init_fn=lambda: 0.0,
        data_fn=lambda i: 1.0)
    rep = run_training_workflow(dag, final, metrics).report
    assert rep.optimizer == ()
    assert rep.results[final] == 3.0


# -- pass invariants: clustering (delayed I/O) ------------------------------


def test_clustering_reduces_kv_sets_on_chain_dag():
    """The delayed-I/O invariant on a chain of fan-in links: with fusion
    and coalescing off, clustering alone must strictly reduce KV ``set``
    operations (the completing arriver never writes its held value)."""
    clustered = OptimizeConfig(fuse_chains=False, coalesce_fanouts=False,
                               cluster_tasks=True)
    base = WukongEngine().compute(chained_fanin_dag(8))
    opt = WukongEngine(
        EngineConfig(optimize=clustered)).compute(chained_fanin_dag(8))
    assert opt.results == base.results == seq_eval(chained_fanin_dag(8))
    # one saved set per fan-in link
    assert opt.kv_stats["puts"] <= base.kv_stats["puts"] - 8


def test_cluster_annotations():
    dag = chained_fanin_dag(4)
    clusters, delayed = compute_clusters(dag)
    assert set(clusters) == set(dag.tasks)          # total assignment
    assert delayed == {f"x{i}" for i in range(1, 5)}  # every fan-in node
    # a fan-in node shares its cluster with its primary (first) parent
    for k in delayed:
        assert clusters[k] == clusters[dag.deps[k][0]]


def test_delayed_fanins_safe_under_retries():
    # seed=18: verified recoverable under the process-stable fault hash
    # (failures at attempt 0 only)
    dag = tree_dag(16)
    cfg = EngineConfig(optimize=ALL_PASSES, faults=FaultConfig(
        task_failure_prob=0.04, max_retries=2, seed=18))
    rep = WukongEngine(cfg).compute(dag)
    assert rep.results == seq_eval(tree_dag(16))


# -- pass invariants: coalescing --------------------------------------------


def test_coalescing_groups_only_true_siblings():
    dag = tree_dag(16)  # leaf pairs share a combine; pairs don't mix
    batches = coalesce_leaves(dag, batch=7)
    for b in batches:
        sigs = {tuple(sorted(dag.children[k])) for k in b}
        assert len(sigs) == 1
        assert len(b) <= 7
    assert sorted(k for b in batches for k in b) == sorted(dag.leaves)


def test_coalescing_reduces_invocations():
    coal = OptimizeConfig(fuse_chains=False, cluster_tasks=False,
                          coalesce_fanouts=True)
    base = WukongEngine().compute(tree_dag(64))
    opt = WukongEngine(EngineConfig(optimize=coal)).compute(tree_dag(64))
    assert opt.results == base.results
    assert opt.executors_invoked < base.executors_invoked


def test_coalescing_chunks_wide_fanout_below_proxy_threshold():
    g = GraphBuilder()
    src = g.add(lambda: 3, name="src")
    outs = [g.add(lambda x, i=i: x * i, src, name=f"m{i}")
            for i in range(32)]
    g.add(lambda *xs: sum(xs), *outs, name="total")
    dag = g.build()
    base = WukongEngine().compute(dag)
    opt = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(dag)
    assert base.results == opt.results
    assert opt.results["total"] == 3 * sum(range(32))
    assert opt.executors_invoked < base.executors_invoked


# -- the acceptance criterion ----------------------------------------------


def test_tree_reduction_64_wide_all_passes_beats_unoptimized():
    """ISSUE acceptance: on a 64-wide tree reduction, all passes enabled
    must show strictly fewer KV ``set`` ops and lower simulated charged_ms
    than the unoptimized run, with results matching sequential evaluation
    on every engine."""
    from repro.apps.tree_reduction import tree_reduction_dag

    def dag64():
        return tree_reduction_dag(128)  # 64 leaf tasks

    want = seq_eval(dag64())
    (root_key,) = want.keys()

    base = WukongEngine().compute(dag64())
    opt = WukongEngine(EngineConfig(optimize=ALL_PASSES)).compute(dag64())
    assert opt.kv_stats["puts"] < base.kv_stats["puts"]
    assert opt.charged_ms < base.charged_ms

    for name, factory in ENGINES:
        got = factory(ALL_PASSES).compute(dag64()).results
        assert got[root_key][0] == want[root_key][0], name


def test_pass_stats_reported():
    rep = WukongEngine(
        EngineConfig(optimize=ALL_PASSES)).compute(mixed_dag())
    names = [s.name for s in rep.optimizer]
    assert names == ["inline_producers", "fuse_chains", "cluster_tasks",
                     "coalesce_fanouts"]
    inline, fuse = rep.optimizer[:2]
    assert inline.after_tasks == inline.before_tasks  # lambdas: no engagement
    assert fuse.after_tasks < fuse.before_tasks


def test_no_passes_is_identity_pipeline():
    dag = mixed_dag()
    compiled = compile_dag(dag, NO_PASSES)
    assert len(compiled) == len(dag)
    assert compiled.clusters == {}
    assert compiled.delayed_fanins == frozenset()
    assert [len(b) for b in compiled.leaf_batches] == [1] * len(dag.leaves)
    rep = WukongEngine().compute(compiled)
    assert rep.results == seq_eval(dag)
