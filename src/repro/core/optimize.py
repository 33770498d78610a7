"""DAG compiler: composable rewrite passes run before scheduling.

The paper attributes WUKONG's wins to shipping static schedules and
keeping data local to executors (§IV-B–C, §V-B); the follow-up work
(*Wukong: A Scalable and Locality-Enhanced Framework for Serverless
Parallel Computing*, PAPERS.md) goes further with task clustering and
delayed I/O to cut KV-store round trips. This module implements that
compiler layer as four composable passes over a ``DAG``:

1. **Producer inlining** (``inline_producers``): a task whose body is a
   bare ``jax.jit`` function of task outputs only absorbs, recursively,
   every dependency that it alone consumes and that is itself such a
   task, up to ``max_fusion_len`` tasks. The group becomes one task keyed
   by its consumer: one jitted program that evaluates the members in
   topological order, so the absorbed values never leave the device
   program and the group costs one dispatch, not one per member. Groups
   of the same structure (member functions and argument wiring) share
   one compiled program. Where the members average more operations on
   their inputs than ``ONE_PROGRAM_MAX_FLOPS``, the device and not the
   dispatches sets the pace, and the task calls them one by one instead.
   Costed wrappers, partials, closures and literal arguments are not
   jitted functions of task outputs, so they never engage.

2. **Linear-chain fusion** (``fuse_chains``): a dependency edge u -> v
   with out-degree(u) == 1 and in-degree(v) == 1 carries a value that
   exactly one consumer will ever read. Maximal runs of such edges are
   collapsed into one fused task keyed by the chain tail, so the
   intermediate values never exist as graph edges at all — they cannot
   hit the KV store, cannot be re-read, and cost zero scheduling
   overhead. Fusion never crosses a fan-in or fan-out boundary: the
   chain head may itself be a fan-in node (the boundary is *before* the
   head) and the tail may fan out (the boundary is *after* the tail),
   but no interior edge touches a node with in-degree or out-degree
   above one.

3. **Task clustering** (``cluster_tasks``): annotates every node with a
   cluster id — the head of the static *become-path* that a Task
   Executor walks (trivial fan-outs and first-child become edges), with
   fan-in nodes joining the cluster of their primary (first) parent.
   The executor uses the annotation to *delay* KV writes at fan-in
   boundaries: arrivals deposit their locally-held inputs atomically
   with the dependency-counter increment (one round trip, not two), and
   the last arriver never writes its own value at all — it keeps the
   object in executor-local memory and carries it through the fan-in.
   This is the delayed-I/O locality optimization from the follow-up
   paper; it deterministically saves one KV ``set`` (plus one base
   round-trip per arriver) at every clustered fan-in node.

4. **Fan-out coalescing** (``coalesce_fanouts``): sibling leaves that
   share an identical child signature are grouped into batches (kept
   below the proxy threshold) so one executor invocation runs the whole
   batch, draining the invoker queue ``batch`` times faster on wide
   fan-outs; the executor applies the same batching to the children it
   invokes at a runtime fan-out.

Every pass is independently switchable through ``OptimizeConfig`` so
§V-B-style factor ablations can measure each one in isolation. Passes
rewrite/annotate only; correctness is preserved by construction: the
optimized DAG computes exactly the same root values as a sequential
topological evaluation of the original DAG (see tests/test_optimize.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Iterable, Mapping

from repro.analysis.dagcheck import check_compiled
from repro.core.dag import DAG, Task, TaskRef


@dataclasses.dataclass(frozen=True)
class OptimizeConfig:
    """Which passes run, and their knobs (all passes default on)."""

    inline_producers: bool = True
    fuse_chains: bool = True
    cluster_tasks: bool = True
    coalesce_fanouts: bool = True
    max_fusion_len: int = 64     # cap on a fused chain or inlined group,
                                 # for retry granularity
    coalesce_batch: int = 7      # max leaves per batched invocation; kept
                                 # below the default proxy threshold (8) so
                                 # batched spawns stay on the fast path


#: Convenience preset: every pass enabled with defaults.
ALL_PASSES = OptimizeConfig()
#: Convenience preset: the identity pipeline (compile_dag returns an
#: annotated but unrewritten graph).
NO_PASSES = OptimizeConfig(
    inline_producers=False, fuse_chains=False, cluster_tasks=False,
    coalesce_fanouts=False,
)


@dataclasses.dataclass(frozen=True)
class PassStats:
    """One row of the compiler report (surfaced in ``JobReport``)."""

    name: str
    before_tasks: int
    after_tasks: int
    detail: str = ""


class CompiledDAG(DAG):
    """A ``DAG`` plus optimizer annotations.

    ``clusters``        — task key -> cluster id (head of its become-path);
                          empty when the clustering pass is off.
    ``delayed_fanins``  — fan-in nodes where executors use the atomic
                          deposit-and-increment protocol (delayed I/O).
    ``leaf_batches``    — tuple of leaf-key tuples; each batch is started
                          by ONE executor invocation. Covers every leaf
                          (singleton batches when coalescing is off).
    ``fused``           — fused task key -> the original keys it replaces,
                          in evaluation order, the key itself last.
    ``pass_stats``      — per-pass before/after report.
    """

    def __init__(
        self,
        tasks: Iterable[Task],
        clusters: Mapping[str, str] | None = None,
        delayed_fanins: Iterable[str] = (),
        leaf_batches: Iterable[tuple[str, ...]] | None = None,
        fused: Mapping[str, tuple[str, ...]] | None = None,
        pass_stats: Iterable[PassStats] = (),
        coalesce_batch: int = 0,
    ):
        super().__init__(tasks)
        self.clusters: dict[str, str] = dict(clusters or {})
        self.delayed_fanins: frozenset[str] = frozenset(delayed_fanins)
        self.leaf_batches: tuple[tuple[str, ...], ...] = (
            tuple(tuple(b) for b in leaf_batches)
            if leaf_batches is not None
            else tuple((leaf,) for leaf in self.leaves)
        )
        self.fused: dict[str, tuple[str, ...]] = dict(fused or {})
        self.pass_stats: tuple[PassStats, ...] = tuple(pass_stats)
        self.coalesce_batch = coalesce_batch


# ---------------------------------------------------------------------------
# Pass 1: producer inlining
# ---------------------------------------------------------------------------


def _is_jitted(fn: Any) -> bool:
    """A ``jax.jit`` function, by duck typing (no JAX import here)."""
    return (callable(getattr(fn, "lower", None))
            and callable(getattr(fn, "trace", None)))


def _inlinable(task: Task) -> bool:
    return (not task.kwargs and _is_jitted(task.fn)
            and all(isinstance(a, TaskRef) for a in task.args))


def find_producer_groups(dag: DAG, max_len: int = 64) -> list[list[str]]:
    """Groups of two or more inlinable tasks, each in evaluation order
    with its consumer (the group's key) last.

    Consumers are visited from the sinks up, so a task is absorbed by its
    one consumer's group before it could root a group of its own. A group
    absorbs a dependency only if the group is that task's sole consumer.
    """
    tasks, deps, children = dag.tasks, dag.deps, dag.children
    ok = {k for k, t in tasks.items() if _inlinable(t)}
    if not ok:
        return []
    taken: set[str] = set()
    groups: list[list[str]] = []
    for root in reversed(dag.topological_order()):
        if root not in ok or root in taken:
            continue
        members = {root}
        frontier = [root]
        while frontier and len(members) < max_len:
            nxt = []
            for k in frontier:
                for d in deps[k]:
                    if (len(members) < max_len and d in ok
                            and len(children[d]) == 1):
                        members.add(d)
                        nxt.append(d)
            frontier = nxt
        if len(members) > 1:
            taken |= members
            groups.append(_evaluation_order(root, members, tasks))
    return groups


def _evaluation_order(root: str, members: set[str],
                      tasks: Mapping[str, Task]) -> list[str]:
    """Post-order from ``root`` through the members, args in order: a
    topological order fixed by the group's structure, not its keys."""
    order: list[str] = []
    seen = {root}
    stack = [(root, iter(tasks[root].args))]
    while stack:
        k, args = stack[-1]
        for a in args:
            if a.key in members and a.key not in seen:
                seen.add(a.key)
                stack.append((a.key, iter(tasks[a.key].args)))
                break
        else:
            stack.pop()
            order.append(k)
    return order


#: Operations that a group's members may average for the group to run as
#: one program: 2^34, about 87 us at a TPU v5e's bf16 peak, against some
#: 280 us of host time to dispatch one program there. Above it each
#: member keeps the device busy for longer than its dispatch takes, so one
#: program saves no waiting, and it costs device time: on the v5e a 4096^2
#: group's second product ran 0.93 ms inside the group against 0.72 alone.
ONE_PROGRAM_MAX_FLOPS = 2.0 ** 34


def _evaluate(fns: tuple[Callable[..., Any], ...],
              wiring: tuple[tuple[tuple[bool, int], ...], ...],
              inputs: tuple[Any, ...],
              wrap: Callable[[Any], Any] = lambda v: v) -> Any:
    """Call the members in evaluation order on the group's inputs and
    earlier members' values, as ``wiring`` says; the last member's value."""
    vals: list[Any] = []
    for fn, args in zip(fns, wiring):
        vals.append(wrap(fn(*[vals[i] if member else inputs[i]
                              for member, i in args])))
    return vals[-1]


@functools.lru_cache(maxsize=256)
def _group_program(fns: tuple[Callable[..., Any], ...],
                   wiring: tuple[tuple[tuple[bool, int], ...], ...]):
    """One jitted program evaluating a group, cached by its structure:
    member functions in evaluation order and, per argument, whether it is
    an earlier member's value or an input, and which. Bounded, since DAGs
    built from per-call jitted closures give a new structure each time.

    Each member's value passes an optimization barrier, so XLA does not
    fuse one member into the next: on a TPU v5e, each 4096^2 block sum
    folded into the product before it took more device time than the two
    programs. The program still places and prefetches its buffers as one
    program, so a member may run slower inside it than alone."""
    import jax

    def program(*inputs: Any) -> Any:
        return _evaluate(fns, wiring, inputs, jax.lax.optimization_barrier)

    counts: dict[str, int] = {}
    for fn in fns:
        name = getattr(fn, "__name__", "fn").strip("_")
        counts[name] = counts.get(name, 0) + 1
    program.__name__ = "inlined_" + "_".join(f"{n}{c}"
                                             for n, c in counts.items())
    return jax.jit(program)


def _operations(jaxpr: Any) -> float:
    """Operations of a jaxpr: 2 m n k for each (m, k) @ (k, n) matrix
    product, batch dimensions included, and one an output element for
    every other equation; a nested jaxpr counts in place of the equation
    that calls it, once (a loop's body is not multiplied by its trips)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    total = 0.0
    for eqn in jaxpr.eqns:
        inner = [p.jaxpr if isinstance(p, ClosedJaxpr) else p
                 for v in eqn.params.values()
                 for p in (v if isinstance(v, tuple) else (v,))
                 if isinstance(p, (ClosedJaxpr, Jaxpr))]
        if inner:
            total += sum(_operations(j) for j in inner)
        elif eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[d] for d in contract)
            total += 2.0 * k * math.prod(eqn.outvars[0].aval.shape)
        else:
            total += sum(math.prod(getattr(v.aval, "shape", ()))
                         for v in eqn.outvars)
    return total


@functools.lru_cache(maxsize=256)
def _flops_per_member(fns: tuple[Callable[..., Any], ...],
                      wiring: tuple[tuple[tuple[bool, int], ...], ...],
                      tree: Any, leaves: tuple[tuple[Any, Any], ...]) -> float:
    """Operations of the members on inputs of these shapes and dtypes,
    counted on their traced jaxpr (the same on every backend), per member."""
    import jax

    specs = tree.unflatten([jax.ShapeDtypeStruct(s, d) for s, d in leaves])
    traced = jax.make_jaxpr(lambda *xs: _evaluate(fns, wiring, xs))(*specs)
    return _operations(traced.jaxpr) / len(fns)


class InlinedGroup:
    """The body of an inlined group's task, named as its ``program``.

    On inputs whose members average fewer than ``ONE_PROGRAM_MAX_FLOPS``
    operations it runs the group's one shared program. Otherwise it calls
    the members one by one, as the graph before the pass would: the group
    then saves the store round trips and scheduling of its absorbed tasks,
    not their dispatches. The count is taken once per group structure and
    input shapes."""

    def __init__(self, fns: tuple[Callable[..., Any], ...],
                 wiring: tuple[tuple[tuple[bool, int], ...], ...]) -> None:
        self.fns, self.wiring = fns, wiring
        self.program = _group_program(fns, wiring)
        self.__name__ = self.program.__name__

    def __call__(self, *inputs: Any) -> Any:
        import jax

        leaves, tree = jax.tree_util.tree_flatten(inputs)
        shapes = tuple((x.shape, x.dtype) if hasattr(x, "dtype")
                       else (jax.typeof(x).shape, jax.typeof(x).dtype)
                       for x in leaves)
        if (_flops_per_member(self.fns, self.wiring, tree, shapes)
                < ONE_PROGRAM_MAX_FLOPS):
            return self.program(*inputs)
        return _evaluate(self.fns, self.wiring, inputs)


def inline_producer_groups(
    dag: DAG, max_len: int = 64
) -> tuple[list[Task], dict[str, tuple[str, ...]]]:
    """Rewrite: each group becomes one task keyed by its consumer, whose
    args are the group's external inputs, de-duplicated, in order of first
    use, and whose fn runs the group (:class:`InlinedGroup`)."""
    drop: set[str] = set()
    replace: dict[str, Task] = {}
    provenance: dict[str, tuple[str, ...]] = {}
    for order in find_producer_groups(dag, max_len):
        index = {k: i for i, k in enumerate(order)}
        inputs: dict[str, int] = {}
        wiring = tuple(
            tuple((True, index[a.key]) if a.key in index
                  else (False, inputs.setdefault(a.key, len(inputs)))
                  for a in dag.tasks[k].args)
            for k in order)
        fns = tuple(dag.tasks[k].fn for k in order)
        root = order[-1]
        drop.update(order[:-1])
        replace[root] = Task(key=root, fn=InlinedGroup(fns, wiring),
                             args=tuple(TaskRef(k) for k in inputs))
        provenance[root] = tuple(order)
    out = [
        replace.get(k, t) for k, t in dag.tasks.items() if k not in drop
    ]
    return out, provenance


# ---------------------------------------------------------------------------
# Pass 2: linear-chain fusion
# ---------------------------------------------------------------------------


def fusible_edges(dag: DAG) -> set[tuple[str, str]]:
    """Edges u->v collapsible without crossing a fan-in/fan-out boundary."""
    return {
        (u, vs[0])
        for u, vs in dag.children.items()
        if len(vs) == 1 and len(dag.deps[vs[0]]) == 1
    }


def find_chains(dag: DAG, max_len: int = 64) -> list[list[str]]:
    """Maximal runs of fusible edges, as key lists (head first).

    Fusible edges form vertex-disjoint paths by construction (a node has
    at most one fusible out-edge and one fusible in-edge), so a simple
    head-scan enumerates them all.
    """
    edges = fusible_edges(dag)
    has_fusible_in = {v for _, v in edges}
    seg_len = max(2, max_len)
    chains: list[list[str]] = []
    for head in dag.tasks:
        if head in has_fusible_in:
            continue  # interior or tail of some chain
        chain = [head]
        while True:
            children = dag.children[chain[-1]]
            if not children or (chain[-1], children[0]) not in edges:
                break
            chain.append(children[0])
        # Disjoint segments of at most seg_len nodes; the edge between two
        # adjacent segments survives as a regular (tail -> next head) edge.
        for i in range(0, len(chain), seg_len):
            seg = chain[i:i + seg_len]
            if len(seg) > 1:
                chains.append(seg)
    return chains


def _make_fused_fn(chain: list[str], tasks: Mapping[str, Task]):
    """One callable running the whole chain; the only graph-visible value
    is the tail's output, so interior values stay on the executor heap."""
    head = tasks[chain[0]]

    def fused(*args: Any, **kwargs: Any) -> Any:
        value = head.fn(*args, **kwargs)
        prev = chain[0]
        for key in chain[1:]:
            t = tasks[key]
            a = [value if isinstance(x, TaskRef) and x.key == prev else x
                 for x in t.args]
            kw = {k: value if isinstance(v, TaskRef) and v.key == prev else v
                  for k, v in t.kwargs.items()}
            value = t.fn(*a, **kw)
            prev = key
        return value

    fused.__name__ = f"fused[{chain[0]}..{chain[-1]}]"
    return fused


def fuse_linear_chains(
    dag: DAG, max_len: int = 64
) -> tuple[list[Task], dict[str, tuple[str, ...]]]:
    """Rewrite: collapse each chain into one task keyed by its tail.

    The fused task inherits the head's args (its in-edges) and the tail's
    key (its out-edges), so the surrounding graph is untouched and root
    keys survive verbatim.
    """
    chains = find_chains(dag, max_len)
    drop: set[str] = set()
    replace: dict[str, Task] = {}
    provenance: dict[str, tuple[str, ...]] = {}
    for chain in chains:
        head, tail = chain[0], chain[-1]
        drop.update(chain[:-1])
        replace[tail] = Task(
            key=tail,
            fn=_make_fused_fn(chain, dag.tasks),
            args=dag.tasks[head].args,
            kwargs=dag.tasks[head].kwargs,
        )
        provenance[tail] = tuple(chain)
    out = [
        replace.get(k, t) for k, t in dag.tasks.items() if k not in drop
    ]
    return out, provenance


# ---------------------------------------------------------------------------
# Pass 3: task clustering (annotation only)
# ---------------------------------------------------------------------------


def compute_clusters(dag: DAG) -> tuple[dict[str, str], frozenset[str]]:
    """Cluster id per node + the set of delayed fan-in nodes.

    A node joins its parent's cluster along edges an executor walks
    without a new invocation: the trivial fan-out / become edge (it is
    the parent's first child) or — for fan-in nodes — the primary
    (first-listed) in-edge, matching the executor that continues through
    the counter. Every other node heads a fresh cluster.
    """
    clusters: dict[str, str] = {}
    delayed: set[str] = set()
    for k in dag.topological_order():
        deps = dag.deps[k]
        if not deps:
            clusters[k] = k
        elif len(deps) == 1:
            parent = deps[0]
            is_become = dag.children[parent] and dag.children[parent][0] == k
            clusters[k] = clusters[parent] if is_become else k
        else:
            clusters[k] = clusters[deps[0]]
            delayed.add(k)  # shares a cluster with its primary parent
    return clusters, frozenset(delayed)


# ---------------------------------------------------------------------------
# Pass 4: fan-out coalescing (annotation only)
# ---------------------------------------------------------------------------


def coalesce_leaves(dag: DAG, batch: int) -> tuple[tuple[str, ...], ...]:
    """Group sibling leaves with an identical child signature into batches
    of at most ``batch`` keys; singleton batches for everything else."""
    groups: dict[tuple[str, ...], list[str]] = {}
    for leaf in dag.leaves:
        groups.setdefault(tuple(sorted(dag.children[leaf])), []).append(leaf)
    batches: list[tuple[str, ...]] = []
    step = max(1, batch)
    for siblings in groups.values():
        for i in range(0, len(siblings), step):
            batches.append(tuple(siblings[i:i + step]))
    return tuple(batches)


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------


def compile_dag(dag: DAG, config: OptimizeConfig | None = None) -> CompiledDAG:
    """Run the enabled passes and return the annotated, rewritten DAG."""
    cfg = config or ALL_PASSES
    stats: list[PassStats] = []
    tasks: Iterable[Task] = dag.tasks.values()
    fused: dict[str, tuple[str, ...]] = {}
    working = dag

    # Each rewrite pass rebuilds the graph only when it changed something:
    # host-side compilation is a measured hot path on wide DAGs that no
    # pass rewrites, like tree reductions.
    if cfg.inline_producers:
        before = len(working)
        task_list, fused = inline_producer_groups(working, cfg.max_fusion_len)
        if fused:
            working = DAG(task_list)
            tasks = working.tasks.values()
        stats.append(PassStats(
            name="inline_producers", before_tasks=before,
            after_tasks=len(working),
            detail=(f"{len(fused)} programs formed, "
                    f"{before - len(working)} tasks absorbed"),
        ))

    if cfg.fuse_chains:
        before = len(working)
        task_list, chains = fuse_linear_chains(working, cfg.max_fusion_len)
        if chains:
            working = DAG(task_list)
            tasks = working.tasks.values()
        # Provenance in original keys: an inlined group fused into a chain
        # contributes its members in place of its own key.
        for tail, chain in chains.items():
            fused[tail] = tuple(x for c in chain for x in fused.pop(c, (c,)))
        stats.append(PassStats(
            name="fuse_chains", before_tasks=before, after_tasks=len(working),
            detail=f"{len(chains)} chains fused",
        ))

    clusters: dict[str, str] = {}
    delayed: frozenset[str] = frozenset()
    if cfg.cluster_tasks:
        clusters, delayed = compute_clusters(working)
        stats.append(PassStats(
            name="cluster_tasks", before_tasks=len(working),
            after_tasks=len(working),
            detail=(f"{len(set(clusters.values()))} clusters, "
                    f"{len(delayed)} delayed fan-ins"),
        ))

    batches: tuple[tuple[str, ...], ...] | None = None
    if cfg.coalesce_fanouts:
        batches = coalesce_leaves(working, cfg.coalesce_batch)
        stats.append(PassStats(
            name="coalesce_fanouts", before_tasks=len(working.leaves),
            after_tasks=len(batches),
            detail=f"{len(working.leaves)} leaves -> "
                   f"{len(batches)} invocations",
        ))

    compiled = CompiledDAG(
        tasks=tasks,
        clusters=clusters,
        delayed_fanins=delayed,
        leaf_batches=batches,
        fused=fused,
        pass_stats=stats,
        coalesce_batch=cfg.coalesce_batch if cfg.coalesce_fanouts else 0,
    )
    # Pre-flight: every annotation the passes produced must be
    # consistent with the rewritten graph (ConsistencyError here means a
    # compiler-pass bug, caught before any executor is invoked).
    check_compiled(compiled)
    return compiled


def ensure_compiled(dag: DAG, config: OptimizeConfig | None) -> DAG:
    """Engine entry point: compile unless disabled or already compiled."""
    if isinstance(dag, CompiledDAG):
        return dag
    if config is None:
        return dag
    return compile_dag(dag, config)
