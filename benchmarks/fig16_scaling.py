"""Fig. 16 (beyond-paper): scaling curve of the simulation clock.

The event-driven clock (PR 6) runs actors as continuations driven from
its ready queue, so simulating a DAG costs generator dispatches instead
of OS threads and context switches. This figure measures how fast the
simulator itself runs, at two levels:

1. **Clock-level tree reduction** — pure actors (one generator per
   leaf/node) on clock queues, no engine around them: the raw cost of a
   frame step.
2. **Engine-level scaling curve** — the real ``WukongEngine`` on tree
   reductions from 8k to 10^6 tasks. The CI gate asserts the 10^5-task
   tier completes in under 30 s of host wall time.

Rows report ``wall_s`` as *host* seconds (the quantity under test —
how fast the simulator itself runs), with the simulated makespan in
``sim_s``. The clock-level rows and the small engine tiers are run
twice and carry a ``deterministic`` bit.
"""
from __future__ import annotations

import sys
import time
from typing import Any

from benchmarks import common
from repro.apps import tree_reduction_dag
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import CostModel, EngineConfig, EventClock, WukongEngine

GATE_SCALE_TASKS = 100_000  # engine tier the wall-budget gate runs at
GATE_SCALE_BUDGET_S = 30.0


def _tree_actors(clock, leaves: int, compute_ms: float):
    """Spawn a pure-actor tree reduction on ``clock``: one generator per
    leaf and per internal node, pairwise-reducing through clock queues.
    Returns the root generator for ``clock.run``."""
    qs = []
    for i in range(leaves):
        q = clock.queue()

        def leaf(q=q):
            yield ("charge", compute_ms)
            q.put(1)

        clock.spawn(leaf, name=f"leaf{i}")
        qs.append(q)
    while len(qs) > 1:
        nxt = []
        for i in range(0, len(qs), 2):
            a_q, b_q, out = qs[i], qs[i + 1], clock.queue()

            def node(a_q=a_q, b_q=b_q, out=out):
                a = yield ("get", a_q, None)
                b = yield ("get", b_q, None)
                yield ("charge", compute_ms)
                out.put(a + b)

            clock.spawn(node, name="node")
            nxt.append(out)
        qs = nxt

    def root(q=qs[0]):
        return (yield ("get", q, None))

    return root()


def _micro_once(leaves: int, compute_ms: float) -> dict[str, Any]:
    clock = EventClock()
    t0 = time.perf_counter()
    total = clock.run(_tree_actors(clock, leaves, compute_ms))
    elapsed = time.perf_counter() - t0
    assert total == leaves
    return {"wall_s": elapsed, "sim_ms": clock.now_ms(),
            "charged_ms": clock.charged_ms, "result": total}


def _engine_once(n: int, compute_ms: float) -> dict[str, Any]:
    dag = tree_reduction_dag(n, compute_ms=compute_ms)
    cfg = EngineConfig(
        cost=CostModel(),
        max_concurrency=max(n, 4096),
        job_timeout_s=1e6,
        # Million-task tiers would hold ~2.5 metric dicts per task;
        # recording is off for the whole curve so tiers are comparable.
        record_metrics=False,
    )
    t0 = time.perf_counter()
    rep = WukongEngine(cfg).compute(dag)
    elapsed = time.perf_counter() - t0
    (_, root), = rep.results.items()
    assert root[0] == tree_reduction_expected(n)
    return {"wall_s": elapsed, "sim_ms": rep.wall_s * 1e3,
            "charged_ms": rep.charged_ms, "kv_stats": rep.kv_stats,
            "tasks": rep.tasks}


def _row(level: str, tasks: int, first: dict,
         second: "dict | None") -> dict[str, Any]:
    """One scaling-curve row. ``wall_s`` is host seconds (best of the
    runs taken); ``deterministic`` compares the simulated quantities of
    two runs bit-for-bit."""
    deterministic = None
    if second is not None:
        deterministic = all(first[k] == second[k]
                            for k in ("sim_ms", "charged_ms"))
    wall = (min(first["wall_s"], second["wall_s"]) if second is not None
            else first["wall_s"])
    sim_s = first["sim_ms"] / 1e3
    row = {
        "label": f"{level}@{tasks}",
        "level": level,
        "tasks": tasks,
        "wall_s": wall,
        "sim_s": sim_s,
        "charged_ms": first["charged_ms"],
        "kv_stats": first.get("kv_stats"),
        "deterministic": deterministic,
        "derived": (f"tasks={tasks} sim_s={sim_s:.1f} "
                    f"charged={first['charged_ms']:.1f}ms"
                    + ("" if deterministic is None
                       else f" deterministic={deterministic}")),
    }
    return row


def run(micro_leaves: "tuple[int, ...]" = (1024, 4096),
        engine_tiers: "tuple[int, ...]" = (8192, 131072),
        compute_ms: float = 1.0) -> list[dict]:
    """``engine_tiers`` are tree-reduction sizes: dag_n - 1 tasks per
    tier."""
    rows: list[dict] = []
    for leaves in micro_leaves:
        first = _micro_once(leaves, compute_ms)
        second = _micro_once(leaves, compute_ms)
        rows.append(_row("clock", 2 * leaves - 1, first, second))
    for n in engine_tiers:
        first = _engine_once(n, compute_ms)
        # The bit-identity repeat is only affordable at the small tiers;
        # big tiers get determinism coverage from the clock-level rows
        # and the slow-marked scale test.
        second = _engine_once(n, compute_ms) if n <= 16384 else None
        rows.append(_row("engine", n - 1, first, second))
    return rows


def scaling_curve(rows: list[dict]) -> list[dict]:
    """The compact tasks-vs-wall-seconds record for BENCH_results.json."""
    return [{k: r[k] for k in ("level", "tasks", "wall_s", "sim_s",
                               "charged_ms", "deterministic")}
            for r in rows]


def check_gates(rows: list[dict]) -> None:
    """The CI scale gates (raise SystemExit on regression):

    - *bit-identity*: every twice-run row must reproduce its simulated
      time and charged ms exactly;
    - *scale budget*: the >= 10^5-task engine tier must complete in
      under 30 s of host wall time.
    """
    for r in rows:
        if r["deterministic"] is False:
            raise SystemExit(
                f"scale regression: {r['label']} not bit-identical across "
                "two runs")

    scale = [r for r in rows if r["level"] == "engine"
             and r["tasks"] >= GATE_SCALE_TASKS]
    if not scale:
        raise SystemExit(
            f"scale regression: no >= {GATE_SCALE_TASKS}-task engine tier "
            "in the fig16 rows")
    worst = max(scale, key=lambda r: r["wall_s"])
    if worst["wall_s"] >= GATE_SCALE_BUDGET_S:
        raise SystemExit(
            f"scale regression: {worst['tasks']}-task tier took "
            f"{worst['wall_s']:.1f}s host wall "
            f"(< {GATE_SCALE_BUDGET_S:g}s required)")

    print(f"# scale gate OK: {worst['tasks']} tasks in "
          f"{worst['wall_s']:.1f}s; twice-run rows bit-identical",
          file=sys.stderr)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI tiers + the scale gates")
    ap.add_argument("--full", action="store_true",
                    help="adds the 10^6-task engine tier")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="merge the fig16 rows + scaling_curve into this "
                         "BENCH_results.json (read-modify-write; lets the "
                         "CI bench-scale job publish the curve without "
                         "re-running every figure)")
    args = ap.parse_args()

    if args.full:
        kwargs = dict(micro_leaves=(1024, 4096, 16384),
                      engine_tiers=(8192, 131072, 1 << 20))
    else:
        kwargs = dict()  # the smoke/CI tiers are the defaults
    rows = run(**kwargs)
    print("name,us_per_call,derived")
    common.emit(rows, "fig16")
    for r in scaling_curve(rows):
        print(f"# {r}", file=sys.stderr)
    if args.json:
        import json
        import os

        from benchmarks.run import _json_row

        snap = {}
        if os.path.exists(args.json):
            with open(args.json) as f:
                snap = json.load(f)
        snap.setdefault("figures", {})["fig16"] = {
            r["label"]: _json_row(r) for r in rows}
        snap["scaling_curve"] = scaling_curve(rows)
        with open(args.json, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# merged fig16 into {args.json}", file=sys.stderr)
    if args.smoke:
        check_gates(rows)


if __name__ == "__main__":
    main()
