"""Shared per-task compute costing for the workload DAG builders.

Workloads price a task's compute from its analytic FLOPs (the paper's
task-granularity methodology, Fig. 4): ``ms_per_flop`` charges simulated
ms on the engine clock via ``simulated_compute`` (free in wall-clock
time).
"""
from __future__ import annotations

from typing import Any, Callable

from repro.core.simclock import simulated_compute


def flop_costed(fn: Callable[..., Any], flops: float,
                ms_per_flop: float = 0.0) -> Callable[..., Any]:
    """Wrap ``fn`` to charge ``flops`` worth of simulated compute before
    running. Returns ``fn`` itself, unwrapped, when ``ms_per_flop <= 0``
    (the DAG compiler inlines only bare jitted producers)."""
    if ms_per_flop <= 0:
        return fn

    def wrapped(*a: Any, **kw: Any) -> Any:
        simulated_compute(flops * ms_per_flop)
        return fn(*a, **kw)

    wrapped.__name__ = getattr(fn, "__name__", "task")
    return wrapped
