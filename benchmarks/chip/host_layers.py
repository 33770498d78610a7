"""What the engine's host profile logged in a traced run's window.

While a profiler session captures, ``WukongEngine.compute`` splits each
job's host time by layer and logs it in ``repro.core.simclock.HOST_LOG``,
with the garbage collector's pauses, on ``time.perf_counter``'s clock:
the window's. A job counts when its record starts inside the window.
Each reading is a mean over those jobs; a program without the log, or a
window it logged no job in, reads None.
"""
from __future__ import annotations


def host_log():
    """The program's log, or None where the program has none."""
    try:
        from repro.core.simclock import HOST_LOG
    except ImportError:
        return None
    return HOST_LOG


def window_jobs(run, log=None) -> list:
    log = host_log() if log is None else log
    if log is None:
        return []
    lo, hi = run.window.start, run.window.end
    return [r for r in log.jobs if lo <= r.start_ns / 1e9 <= hi]


def layer_ms(run, layer: str, log=None) -> float | None:
    """Mean host ms per window job in ``layer``."""
    jobs = window_jobs(run, log)
    return sum(r.layers_ns[layer] for r in jobs) / len(jobs) / 1e6 if jobs else None


def frame_steps(run, log=None) -> float | None:
    """Mean frame steps per window job."""
    jobs = window_jobs(run, log)
    return sum(r.frame_steps for r in jobs) / len(jobs) if jobs else None


def gc_ms(run, log=None) -> float | None:
    """Collector pauses that start inside the window, in ms per window job."""
    log = host_log() if log is None else log
    jobs = window_jobs(run, log)
    if not jobs:
        return None
    lo, hi = run.window.start, run.window.end
    return 1e3 * sum(d for t, d in log.gc_pauses if lo <= t <= hi) / len(jobs)
