"""Simulated time: one deterministic discrete-event clock.

Every engine layer (KV store, executors, invoker pools, schedulers, the
fault monitor) charges FaaS latency on the clock instead of calling
``time.sleep``/``time.monotonic`` directly. ``EventClock`` is a
continuation/event-driven scheduler: actors are *frames* — generators
yielding effect tuples — driven from a single ready queue by one driver
thread. No OS thread per actor, so a million-task DAG simulates without
exhausting threads, and simulated seconds cost no wall time.

Effect protocol
---------------

Actor logic is written as generator functions yielding effect tuples;
the clock decides how each effect blocks:

- ``("charge", ms)``    — bill ``ms`` simulated ms and advance time.
- ``("get", q, t)``     — blocking ``q.get(timeout=t)`` (seconds;
  ``None`` = forever). ``queue.Empty`` is raised at the yield site.
- ``("acquire", lock)`` — blocking lock acquire (release is a direct
  ``lock.release()`` call).
- ``("wait", ev, t)``   — blocking ``ev.wait(timeout=t)`` (seconds);
  the yield evaluates to the flag.
- ``("flush",)``        — advance time past charges deferred by
  non-yielding code (``simulated_compute`` inside a task function).
- ``("sleep", ms)``     — advance simulated time without billing.

Non-suspending operations (``q.put``, ``ev.set``, ``lock.release``,
``pool.submit``, ``clock.spawn``) are direct calls. Inside a frame the
generator IS the continuation and effects park the frame in the
scheduler; a thread outside the frames drives a generator through
``run_effects``, which maps each effect onto the blocking primitive.

Determinism contract: frames are scheduled FIFO in the order they
became ready; timers fire in (deadline, registration-seq) order;
queue/lock waiters are served FIFO. Any randomness (invoke-latency
jitter, cold starts, fault injection) is drawn from counters/keys hashed
with seeds — never from wall time — so two runs of the same job produce
identical traces.

Threads that never registered as actors (unit tests driving the KV store
directly, external callers) degrade gracefully: their charges accumulate
``charged_ms`` without advancing simulated time, and their blocking
waits use real condition variables with real timeouts.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import heapq
import itertools
import queue as _queue
import sys
import threading
import time
import traceback
from collections import deque
from types import GeneratorType
from typing import Any, Callable

__all__ = [
    "HOST_LOG",
    "EventClock",
    "EventPool",
    "HostLog",
    "HostProfile",
    "HostRecord",
    "charge_meter",
    "host_span",
    "in_layer",
    "run_effects",
    "simulated_compute",
    "task_clock",
]


# ---------------------------------------------------------------------------
# Frame-local context.
#
# On the EventClock many logical actors share ONE driver thread, so
# anything formerly thread-local (the task clock, the billing tap, the
# kv stats sink) must follow the *frame* instead: when frame A suspends
# mid-scope and frame B runs, B must not observe A's context. The
# driver publishes the currently-stepping frame here; thread-locals
# remain the fallback for threads outside the frames.
# ---------------------------------------------------------------------------

_frame_ctx = threading.local()


def _current_frame() -> "_Frame | None":
    return getattr(_frame_ctx, "frame", None)


# ---------------------------------------------------------------------------
# Task-payload compute charging.
#
# Workload DAGs (tree reduction, GEMM, SVD, SVC) declare per-task compute
# duration in *simulated* ms. The executor installs the engine's clock
# around each task-function call; `simulated_compute` charges the
# duration on whatever clock is installed. Outside an engine (sequential
# reference evaluation in tests) it is free: reference results never
# depend on timing.
# ---------------------------------------------------------------------------

_task_clock = threading.local()


class task_clock:
    """Context manager installing ``clock`` as the current task clock."""

    def __init__(self, clock: "EventClock | None"):
        self.clock = clock

    def __enter__(self) -> None:
        frame = _current_frame()
        self._frame = frame
        if frame is not None:
            self._prev = frame.task_clock
            frame.task_clock = self.clock
        else:
            self._prev = getattr(_task_clock, "clock", None)
            _task_clock.clock = self.clock
        # Host profile (HostProfile below): the task function's own span
        # and layer. It never encloses a yield: the task function cannot
        # suspend.
        prof = getattr(self.clock, "host_profile", None)
        self._span = None if prof is None else _HostSpan(prof, *prof.task)
        if self._span is not None:
            self._span.__enter__()

    def __exit__(self, *exc: Any) -> None:
        if self._span is not None:
            self._span.__exit__(*exc)
        if self._frame is not None:
            self._frame.task_clock = self._prev
        else:
            _task_clock.clock = self._prev


def simulated_compute(ms: float) -> None:
    """Charge ``ms`` simulated milliseconds of task compute on the
    engine clock running this task (no-op outside an engine)."""
    frame = _current_frame()
    if frame is not None:
        clock = frame.task_clock
    else:
        clock = getattr(_task_clock, "clock", None)
    if clock is not None and ms > 0:
        clock.charge(ms)


# ---------------------------------------------------------------------------
# Per-thread charge metering (billing).
#
# The platform model bills an invocation the simulated time its body
# *charges* while running — not a wall-clock delta — so billed cost is
# as deterministic as the charges. The tap lives here so the platform
# layer never has to patch clock internals. Inside a frame the
# accumulator rides on the frame (the body suspends and resumes inside
# the metered scope); outside one, on the thread.
# ---------------------------------------------------------------------------

_charge_tap = threading.local()


class charge_meter:
    """Context manager accumulating this actor's clock charges into
    ``acc[0]`` (a single-element list). Nesting restores the previous
    accumulator on exit; charges while nested land in the innermost."""

    def __init__(self, acc: "list[float]"):
        self.acc = acc

    def __enter__(self) -> "list[float]":
        frame = _current_frame()
        self._frame = frame
        if frame is not None:
            self._prev = frame.charge_acc
            frame.charge_acc = self.acc
        else:
            self._prev = getattr(_charge_tap, "acc", None)
            _charge_tap.acc = self.acc
        return self.acc

    def __exit__(self, *exc: Any) -> None:
        if self._frame is not None:
            self._frame.charge_acc = self._prev
        else:
            _charge_tap.acc = self._prev


# ---------------------------------------------------------------------------
# Host-time profile of one job, by layer (opt-in, like ``tracer``).
#
# The simulated clock says what the modelled cloud would charge; it says
# nothing of where this host spends its time. A HostProfile attached to an
# EventClock (``clock.host_profile``; None by default, and then every hook
# below is one None test) reads the host clock at each frame step, at each
# entry into and return from a method wrapped by ``in_layer``, and at each
# span with a layer, and charges the time in between to one layer. Which
# layers there are, which a frame charges and what the spans are called is
# the caller's to say (``WukongEngine.compute``); the profile charges what
# a frame's ``layer`` names (set by ``spawn(..., layer=)``), and the
# ``frames`` layer where it names none. Spans go to the caller's
# ``annotate`` (the profiler's ``TraceAnnotation``), never across a yield:
# frames interleave on one thread, and a span held across a suspension
# would overlap its siblings. A layer rides on the frame (like
# ``charge_meter``), so it survives a suspension.
#
# This module is the one place that reads the host clock; the profile
# reads it only while attached, and never changes a simulated quantity.
# ---------------------------------------------------------------------------

_clock_ns = time.perf_counter_ns  # the host clock HostProfile reads


@dataclasses.dataclass(frozen=True)
class HostRecord:
    """One profiled job, on ``time.perf_counter``'s clock (in ns).

    ``layers_ns`` sums to ``end_ns - start_ns``; ``frame_steps`` is the
    clock's ``switches`` during the job."""

    job: int
    start_ns: int
    end_ns: int
    layers_ns: dict[str, int]
    frame_steps: int


class HostLog:
    """This process's finished job profiles and the garbage collector's
    pauses while a profiler session captured, bounded by count, oldest
    dropped first (a record is a few hundred bytes, a pause a pair of
    floats: some 60 MB when both are full). Process-wide (``HOST_LOG``):
    whoever times the jobs reads it by their window on
    ``time.perf_counter``'s clock."""

    JOBS_MAX = 1 << 16
    GC_MAX = 1 << 18

    def __init__(self) -> None:
        self.jobs: "deque[HostRecord]" = deque(maxlen=self.JOBS_MAX)
        # (start_s, seconds) of each collection
        self.gc_pauses: "deque[tuple[float, float]]" = deque(
            maxlen=self.GC_MAX)
        self._capturing: "Callable[[], bool] | None" = None
        self._gc_start: float | None = None

    def hook_gc(self, capturing: Callable[[], bool]) -> None:
        """Time every collection from now on while ``capturing()`` says
        a session captures (the hook stays; outside a session it costs
        that one check)."""
        if self._capturing is None:
            self._capturing = capturing
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = (time.perf_counter() if self._capturing()
                              else None)
        elif self._gc_start is not None:
            self.gc_pauses.append(
                (self._gc_start, time.perf_counter() - self._gc_start))
            self._gc_start = None


HOST_LOG = HostLog()


class _HostSpan:
    """A span of a HostProfile on the caller's trace; with ``layer``, it
    charges that layer and then gives back the one it found. It encloses
    no yield, so no other frame's step falls inside it."""

    __slots__ = ("prof", "name", "layer", "ann", "outer")

    def __init__(self, prof: "HostProfile", name: str, layer: str | None):
        self.prof = prof
        self.name = name
        self.layer = layer

    def __enter__(self) -> None:
        self.ann = self.prof._annotate(self.name)
        self.ann.__enter__()
        if self.layer is not None:
            self.outer = self.prof._switch(self.layer)

    def __exit__(self, *exc: Any) -> None:
        if self.layer is not None:
            self.prof._switch(self.outer)
        self.ann.__exit__(*exc)


_NO_SPAN = contextlib.nullcontext()


def host_span(prof: "HostProfile | None", name: str,
              layer: str | None = None) -> Any:
    """Context manager: span ``name`` of ``prof``, charging ``layer`` if
    given; nothing when ``prof`` is None. It must enclose no yield."""
    return _NO_SPAN if prof is None else _HostSpan(prof, name, layer)


class HostProfile:
    """Host time of one job on an ``EventClock``, split by layer.

    Constructing one attaches it as ``clock.host_profile`` and starts the
    job; ``finish`` detaches it and logs the job's ``HostRecord``. In
    between, every host nanosecond is charged to one of ``layers``: inside
    a frame step, the frame's layer (``frames`` if its spawn named none);
    between steps, ``idle``; inside a span with a layer (``host_span``;
    ``task`` names the span and layer of a task function under
    ``task_clock``), that layer. ``annotate(name)`` gives a span's context
    manager on the caller's trace."""

    def __init__(self, clock: "EventClock", job: int,
                 annotate: Callable[[str], Any], layers: tuple[str, ...], *,
                 idle: str, frames: str, task: tuple[str, str]):
        self.clock = clock
        self.job = job
        self._annotate = annotate
        self.layers_ns = dict.fromkeys(layers, 0)
        self._frames = frames
        self.task = task
        self._idle = self._layer = idle
        self._frame: "_Frame | None" = None  # the frame stepping now
        self._switches0 = clock.switches
        self._mark = self.start_ns = _clock_ns()
        clock.host_profile = self

    def finish(self) -> HostRecord:
        """Detach, and log and return the job's record."""
        self.clock.host_profile = None
        self._switch(self._layer)
        record = HostRecord(self.job, self.start_ns, self._mark,
                            self.layers_ns,
                            self.clock.switches - self._switches0)
        HOST_LOG.jobs.append(record)
        return record

    def _switch(self, layer: str) -> str:
        """Charge the time since the last switch to the layer being left,
        and enter ``layer``; return the layer left."""
        now = _clock_ns()
        left = self._layer
        self.layers_ns[left] += now - self._mark
        self._mark = now
        self._layer = layer
        return left

    def step_begin(self, frame: "_Frame") -> None:
        self._frame = frame
        layer = frame.layer
        if layer is None:
            layer = frame.layer = self._frames
        self._switch(layer)

    def step_end(self) -> None:
        self._frame = None
        self._switch(self._idle)


def in_layer(layer: str, op: Callable[..., Any]) -> Callable[..., Any]:
    """The effect-generator method ``op`` of an object with a ``clock``,
    charged to ``layer`` of the HostProfile attached to that clock:
    pushed on the running frame at entry and popped at return, so the
    layer survives the method's yields."""

    @functools.wraps(op)
    def timed(obj: Any, *args: Any, **kwargs: Any) -> Any:
        prof = obj.clock.host_profile
        frame = None if prof is None else prof._frame
        if frame is None:  # no profile, or outside the frames
            return (yield from op(obj, *args, **kwargs))
        outer, frame.layer = frame.layer, layer
        prof._switch(layer)
        try:
            return (yield from op(obj, *args, **kwargs))
        finally:
            frame.layer = outer
            # A generator closed while its frame is not stepping (collected
            # after its job) gives the layer back without charging.
            if frame is prof._frame:
                prof._switch(outer)

    return timed


# ---------------------------------------------------------------------------
# External threads
# ---------------------------------------------------------------------------


def run_effects(clock: "EventClock", gen: Any) -> Any:
    """Interpret an effect generator on the calling thread, which is not
    one of ``clock``'s frames (tests, ``_ResultWaiter.wait``,
    ``FanoutProxy.close``, the blocking KV wrappers): each effect maps
    onto the primitive's blocking call. Returns the generator's value.
    Non-generators pass through unchanged."""
    if not isinstance(gen, GeneratorType):
        return gen
    if _current_frame() is not None:
        raise RuntimeError(
            "run_effects() called inside an event-driven frame; compose "
            "generators with 'yield from' instead")
    value: Any = None
    exc: BaseException | None = None
    while True:
        try:
            if exc is not None:
                pending, exc = exc, None
                eff = gen.throw(pending)
            else:
                eff = gen.send(value)
            value = None
        except StopIteration as stop:
            return stop.value
        tracer = clock.tracer
        if tracer is not None:
            tracer.record(threading.current_thread().name, eff, gen)
        kind = eff[0]
        if kind == "charge":
            clock.charge(eff[1])
        elif kind == "get":
            try:
                value = eff[1].get(timeout=eff[2])
            except _queue.Empty as empty:
                exc = empty
        elif kind == "acquire":
            eff[1].acquire()
        elif kind == "wait":
            value = eff[1].wait(eff[2])
        elif kind == "flush":
            pass  # a thread defers no charges
        elif kind == "sleep":
            clock.sleep_ms(eff[1])
        else:
            raise RuntimeError(f"unknown clock effect {eff!r}")


# ---------------------------------------------------------------------------
# The clock: continuation frames on one driver thread
# ---------------------------------------------------------------------------

_WAKE_SIGNAL = "signal"
_WAKE_TIMEOUT = "timeout"


class _Timer:
    """Heap entry waking frame ``owner`` at a simulated deadline."""

    __slots__ = ("deadline", "owner", "cancelled")

    def __init__(self, deadline: float, owner: Any):
        self.deadline = deadline
        self.owner = owner
        self.cancelled = False

    def __lt__(self, other: "_Timer") -> bool:  # heap tiebreak
        return (self.deadline, self.owner.seq) < (
            other.deadline, other.owner.seq)


class _ExternalWaiter:
    """A thread outside the frames blocked on a clock primitive (tests,
    ``run_effects`` callers). It waits on a real condition with a real
    timeout and does not hold back simulated time."""

    __slots__ = ("cond", "signalled")

    def __init__(self, mutex: "threading.Lock | threading.RLock"):
        self.cond = threading.Condition(mutex)
        self.signalled = False


class EventPool:
    """Executor-runtime stand-in for ``ThreadPoolExecutor``: worker
    frames are created lazily up to ``max_workers``, so an 8k-task sweep
    only materializes as many workers as are ever simultaneously busy.
    Queued bodies do NOT hold back simulated time — a full pool models
    the provider's concurrency limit."""

    def __init__(self, clock: "EventClock", max_workers: int):
        self._clock = clock
        self._max_workers = max(1, max_workers)
        self._q = clock.queue()
        self._state_lock = threading.Lock()
        self._workers = 0
        self._idle = 0
        self._closed = False

    def submit(self, fn: Callable[[], Any]) -> None:
        with self._state_lock:
            if self._closed:
                raise RuntimeError("cannot schedule new futures after "
                                   "shutdown")
            spawn = self._idle == 0 and self._workers < self._max_workers
            if spawn:
                self._workers += 1
                n = self._workers
        self._q.put(fn)
        if spawn:
            self._clock.spawn(self._worker, name=f"vpool-{n}")

    def _worker(self) -> Any:
        while True:
            with self._state_lock:
                self._idle += 1
            item = yield ("get", self._q, None)
            with self._state_lock:
                self._idle -= 1
            if item is None:
                return
            r = item()
            if isinstance(r, GeneratorType):
                yield from r

    def shutdown(self, wait: bool = False,
                 cancel_futures: bool = True) -> None:
        with self._state_lock:
            if self._closed:
                return
            self._closed = True
            n = self._workers
        if cancel_futures:
            # Drop queued-but-unstarted bodies (the ThreadPoolExecutor
            # contract). Before this, a torn-down job's queued executors still ran
            # to completion behind the shutdown sentinels — harmless when
            # the clock died with the job, a capacity leak once
            # platform and store outlive it.
            self._q.drain()
        for _ in range(n):
            self._q.put(None)


class _Frame:
    """One logical actor on the EventClock: a (not-yet-started) body or
    its live generator, plus the park/wake state the driver needs."""

    __slots__ = ("seq", "fn", "gen", "name", "wait", "wake_reason", "timer",
                 "deferred_ms", "charge_acc", "task_clock", "sink",
                 "layer", "done", "root", "result", "exc")

    def __init__(self, seq: int, fn: "Callable[[], Any] | None",
                 name: str, root: bool = False):
        self.seq = seq
        self.fn = fn
        self.gen: Any = None
        self.name = name
        self.wait: "tuple[Any, ...] | None" = None
        self.wake_reason: str | None = None
        self.timer: _Timer | None = None
        self.deferred_ms = 0.0   # charges awaiting a ("flush",)
        self.charge_acc: "list[float] | None" = None
        self.task_clock: Any = None
        self.sink: Any = None    # kv-stats sink (namespace mirroring)
        self.layer: "str | None" = None  # HostProfile: the layer charged
        self.done = False
        self.root = root
        self.result: Any = None
        self.exc: BaseException | None = None


class EventClock:
    """The discrete-event simulation clock. Actors are *frames* — effect
    generators — dispatched FIFO from one ready deque by whichever thread
    called ``run()``; no OS thread per actor. Events are ordered FIFO
    for ready frames, by (deadline, spawn seq) for timers and FIFO for
    waiters, with one waiter woken per ``put``.

    Charges issued by non-yielding code inside a frame (a task function
    calling ``simulated_compute``) are *deferred*: billed immediately,
    applied to simulated time at the next suspension or explicit
    ``("flush",)`` effect.

    External (non-frame) threads registered via ``actor()`` drive the
    frame scheduler forward with their charges; unregistered, they bill
    without advancing time and block on real condition variables."""

    def __init__(self) -> None:
        self._charge_lock = threading.Lock()
        self.charged_ms = 0.0
        # Opt-in determinism sanitizer (repro.analysis.divergence.Tracer,
        # duck-typed so the clock never imports the analysis package):
        # when set, every freshly generated effect is journaled via
        # tracer.record(actor, effect, gen). None is free.
        self.tracer: Any = None
        # Opt-in host-time profile of the running job (HostProfile).
        # None is free.
        self.host_profile: "HostProfile | None" = None
        # RLock: frame code runs under the driver's mutex and re-enters
        # it through every primitive call (put/set/release/spawn).
        self._mutex = threading.RLock()
        self._cond = threading.Condition(self._mutex)
        self._now = 0.0
        self._seq = itertools.count()
        self._ready: "deque[_Frame]" = deque()
        self._timers: list[_Timer] = []
        self._driving = False
        self._external_actors: dict[int, int] = {}  # ident -> depth
        self.switches = 0        # frame dispatches (scheduler cost metric)
        self.actors_spawned = 0  # total frames spawned

    # -- introspection ------------------------------------------------------
    def now_ms(self) -> float:
        return self._now

    def _account(self, ms: float) -> None:
        """Bill ``ms``: to the clock, and to the innermost
        ``charge_meter`` of the running frame or thread."""
        with self._charge_lock:
            self.charged_ms += ms
        frame = _current_frame()
        if frame is not None:
            acc = frame.charge_acc
        else:
            acc = getattr(_charge_tap, "acc", None)
        if acc is not None:
            acc[0] += ms

    # -- driver -------------------------------------------------------------
    def run(self, gen: Any) -> Any:
        """Drive ``gen`` as a root frame until it completes, then drain
        any frames it made ready (sentinel cleanup), and return its
        value. Frames still parked on timers stay parked — exactly like
        leftover thread actors — and resume on the next ``run()``."""
        if not isinstance(gen, GeneratorType):
            return gen
        if _current_frame() is not None:
            raise RuntimeError(
                "EventClock.run() called from inside a frame; compose "
                "generators with 'yield from' instead")
        with self._mutex:
            if self._driving:
                raise RuntimeError("EventClock is already being driven")
            root = _Frame(next(self._seq), None, "root", root=True)
            root.gen = gen
            # A thread inside a charge_meter bills what it drives there.
            root.charge_acc = getattr(_charge_tap, "acc", None)
            self._ready.append(root)
            self._driving = True
            try:
                self._drive(root)
            finally:
                self._driving = False
        if root.exc is not None:
            raise root.exc
        return root.result

    def _drive(self, root: _Frame) -> None:
        ready = self._ready
        timers = self._timers
        while not root.done:
            if ready:
                self._dispatch(ready.popleft())
                continue
            while timers and timers[0].cancelled:
                heapq.heappop(timers)
            if timers:
                timer = heapq.heappop(timers)
                self._now = max(self._now, timer.deadline)
                frame = timer.owner
                frame.timer = None
                frame.wake_reason = _WAKE_TIMEOUT
                ready.append(frame)
                continue
            # Fully event-blocked: idle until an external stimulus.
            self._cond.wait()
        while ready:
            # Root finished: run frames its teardown made ready (pool
            # sentinels, lane shutdowns) so they don't leak into the
            # next job's ready order; timer-parked frames stay parked.
            self._dispatch(ready.popleft())

    def _dispatch(self, frame: _Frame) -> None:
        self.switches += 1
        wait, frame.wait = frame.wait, None
        reason, frame.wake_reason = frame.wake_reason, None
        if wait is None:  # first dispatch
            self._step(frame, None, None, None)
            return
        kind = wait[0]
        if kind == "get":
            q, deadline = wait[1], wait[2]
            if reason == _WAKE_TIMEOUT:
                try:
                    q._waiters.remove(frame)
                except ValueError:
                    pass
                self._step(frame, None, _queue.Empty(), None)
                return
            if q._items:
                self._step(frame, q._items.pop(0), None, None)
                return
            # Signalled but the item was taken: wait out the remainder
            # (EventQueue.get re-checks the same way).
            remaining = None if deadline is None else deadline - self._now
            if remaining is not None and remaining <= 0:
                self._step(frame, None, _queue.Empty(), None)
                return
            q._waiters.append(frame)
            self._park(frame, ("get", q, deadline), remaining)
            return
        if kind == "wait":
            ev = wait[1]
            if reason == _WAKE_TIMEOUT:
                try:
                    ev._waiters.remove(frame)
                except ValueError:
                    pass
            self._step(frame, ev._flag, None, None)
            return
        if kind == "retire":
            self._finalize(frame)
            return
        if kind == "replay":
            self._step(frame, None, None, wait[1])
            return
        # "resume" (charge/flush/sleep) or "acquire" (woken owning)
        self._step(frame, None, None, None)

    def _park(self, frame: _Frame, wait: "tuple[Any, ...]",
              timeout_ms: float | None) -> None:
        frame.wait = wait
        if timeout_ms is not None:
            timer = _Timer(self._now + max(0.0, timeout_ms), frame)
            frame.timer = timer
            heapq.heappush(self._timers, timer)

    def _make_ready(self, frame: _Frame) -> None:
        if frame.timer is not None:
            frame.timer.cancelled = True
            frame.timer = None
        frame.wake_reason = _WAKE_SIGNAL
        self._ready.append(frame)
        self._cond.notify_all()  # wake an idle driver

    def _defer_flush(self, frame: _Frame, eff: "tuple[Any, ...]") -> None:
        """A suspending effect arrived with compute charges still
        deferred: advance time past them first, then replay the effect,
        so the time trajectory is the one those charges would give had
        they advanced time when issued."""
        self._park(frame, ("replay", eff), frame.deferred_ms)
        frame.deferred_ms = 0.0

    def _step(self, frame: _Frame, value: Any, exc: "BaseException | None",
              replay: "tuple[Any, ...] | None") -> None:
        _frame_ctx.frame = frame
        prof = self.host_profile
        if prof is not None:
            prof.step_begin(frame)
        try:
            gen = frame.gen
            if gen is None:
                try:
                    r = frame.fn()  # type: ignore[misc]
                except BaseException as e:
                    self._fail(frame, e)
                    return
                frame.fn = None
                if not isinstance(r, GeneratorType):
                    self._retire(frame, r)
                    return
                frame.gen = gen = r
            while True:
                if replay is not None:
                    eff, replay = replay, None
                else:
                    try:
                        if exc is not None:
                            pending, exc = exc, None
                            eff = gen.throw(pending)
                        else:
                            eff = gen.send(value)
                        value = None
                    except StopIteration as stop:
                        self._retire(frame, stop.value)
                        return
                    except BaseException as e:
                        self._fail(frame, e)
                        return
                    # Journal only freshly generated effects — a replayed
                    # effect (deferred-flush re-issue) was already
                    # recorded when the generator first yielded it.
                    tracer = self.tracer
                    if tracer is not None:
                        tracer.record(
                            f"{frame.name or 'frame'}#{frame.seq}", eff, gen)
                kind = eff[0]
                if kind == "charge":
                    ms = eff[1]
                    if ms <= 0:
                        continue
                    self._account(ms)
                    self._park(frame, ("resume",), ms + frame.deferred_ms)
                    frame.deferred_ms = 0.0
                    return
                if kind == "get":
                    if frame.deferred_ms > 0.0:
                        self._defer_flush(frame, eff)
                        return
                    q, timeout = eff[1], eff[2]
                    if q._items:
                        value = q._items.pop(0)
                        continue
                    if timeout is not None and timeout <= 0:
                        exc = _queue.Empty()
                        continue
                    deadline = (None if timeout is None
                                else self._now + timeout * 1e3)
                    q._waiters.append(frame)
                    self._park(frame, ("get", q, deadline),
                               None if timeout is None else timeout * 1e3)
                    return
                if kind == "acquire":
                    if frame.deferred_ms > 0.0:
                        self._defer_flush(frame, eff)
                        return
                    lk = eff[1]
                    if lk._owner is None:
                        lk._owner = frame
                        continue
                    lk._waiters.append(frame)
                    self._park(frame, ("acquire", lk), None)
                    return
                if kind == "wait":
                    if frame.deferred_ms > 0.0:
                        self._defer_flush(frame, eff)
                        return
                    ev, timeout = eff[1], eff[2]
                    if ev._flag:
                        value = True
                        continue
                    ev._waiters.append(frame)
                    self._park(frame, ("wait", ev),
                               None if timeout is None else timeout * 1e3)
                    return
                if kind == "flush":
                    if frame.deferred_ms > 0.0:
                        self._park(frame, ("resume",), frame.deferred_ms)
                        frame.deferred_ms = 0.0
                        return
                    continue
                if kind == "sleep":
                    self._park(frame, ("resume",),
                               max(0.0, eff[1]) + frame.deferred_ms)
                    frame.deferred_ms = 0.0
                    return
                self._fail(frame, RuntimeError(
                    f"unknown clock effect {eff!r}"))
                return
        finally:
            _frame_ctx.frame = None
            if prof is not None:
                prof.step_end()

    def _retire(self, frame: _Frame, result: Any) -> None:
        frame.result = result
        if frame.deferred_ms > 0.0:
            # Auto-flush trailing compute charges: the frame's time
            # footprint includes them.
            self._park(frame, ("retire",), frame.deferred_ms)
            frame.deferred_ms = 0.0
            return
        self._finalize(frame)

    def _finalize(self, frame: _Frame) -> None:
        frame.done = True
        frame.gen = None
        frame.fn = None

    def _fail(self, frame: _Frame, exc: BaseException) -> None:
        frame.gen = None
        frame.fn = None
        frame.done = True
        if frame.root:
            frame.exc = exc
            return
        # An exception escaping a spawned actor body is reported (as the
        # threading excepthook would), not raised into the scheduler.
        print(f"Exception in frame {frame.name!r}:", file=sys.stderr)
        traceback.print_exception(type(exc), exc, exc.__traceback__)

    # -- actor lifecycle ----------------------------------------------------
    def spawn(self, fn: Callable[[], Any], name: str = "",
              layer: str | None = None) -> None:
        with self._mutex:
            frame = _Frame(next(self._seq), fn, name)
            frame.layer = layer
            self._ready.append(frame)
            self.actors_spawned += 1
            self._cond.notify_all()

    class _ExternalActorContext:
        def __init__(self, clock: "EventClock"):
            self.clock = clock

        def __enter__(self) -> None:
            ident = threading.get_ident()
            with self.clock._mutex:
                actors = self.clock._external_actors
                actors[ident] = actors.get(ident, 0) + 1

        def __exit__(self, *exc: Any) -> None:
            ident = threading.get_ident()
            with self.clock._mutex:
                actors = self.clock._external_actors
                depth = actors.get(ident, 0) - 1
                if depth <= 0:
                    actors.pop(ident, None)
                else:
                    actors[ident] = depth

    def actor(self) -> "_ExternalActorContext":
        """Register the calling (external) thread as an actor: its
        charges drive the frame scheduler, advancing simulated time and
        firing parked frames' timers."""
        return EventClock._ExternalActorContext(self)

    # -- time ---------------------------------------------------------------
    def charge(self, ms: float) -> None:
        if ms <= 0:
            return
        frame = _current_frame()
        if frame is not None:
            # Non-yielding code inside a frame (simulated_compute in a
            # task function): bill now, advance at the next suspension.
            self._account(ms)
            frame.deferred_ms += ms
            return
        if threading.get_ident() in self._external_actors:
            def once() -> Any:
                yield ("charge", ms)

            self.run(once())
            return
        self._account(ms)

    def sleep_ms(self, ms: float) -> None:
        frame = _current_frame()
        if frame is not None:
            frame.deferred_ms += max(0.0, ms)
            return
        if threading.get_ident() in self._external_actors:
            def once() -> Any:
                yield ("sleep", ms)

            self.run(once())

    # -- primitives ---------------------------------------------------------
    def queue(self) -> "EventQueue":
        return EventQueue(self)

    def lock(self) -> "EventLock":
        return EventLock(self)

    def event(self) -> "EventEvent":
        return EventEvent(self)

    def pool(self, max_workers: int) -> EventPool:
        return EventPool(self, max_workers)


class EventQueue:
    """``queue.Queue``-compatible FIFO: frames suspend via
    ``("get", q, timeout)`` effects (simulated-seconds timeout);
    external threads block on real condvars with real timeouts."""

    def __init__(self, clock: EventClock):
        self._clock = clock
        self._items: list[Any] = []
        self._waiters: list[Any] = []  # _Frame | _ExternalWaiter, FIFO

    def put(self, item: Any) -> None:
        clock = self._clock
        with clock._mutex:
            self._items.append(item)
            if self._waiters:
                waiter = self._waiters.pop(0)
                if isinstance(waiter, _ExternalWaiter):
                    waiter.signalled = True
                    waiter.cond.notify()
                else:
                    clock._make_ready(waiter)

    def get(self, timeout: float | None = None) -> Any:
        if _current_frame() is not None:
            raise RuntimeError(
                "blocking get() inside a frame would deadlock the "
                "driver; yield ('get', q, timeout) instead")
        clock = self._clock
        with clock._mutex:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while not self._items:
                waiter = _ExternalWaiter(clock._mutex)
                self._waiters.append(waiter)
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    self._waiters.remove(waiter)
                    raise _queue.Empty
                if not waiter.cond.wait(remaining):
                    if waiter in self._waiters:
                        self._waiters.remove(waiter)
                    if not waiter.signalled:
                        raise _queue.Empty
            return self._items.pop(0)

    def empty(self) -> bool:
        with self._clock._mutex:
            return not self._items

    def drain(self) -> "list[Any]":
        with self._clock._mutex:
            items, self._items = self._items, []
            return items


class EventLock:
    """Transfer-lane lock. Frames acquire via
    ``("acquire", lock)`` effects; ``release`` is a direct call with
    FIFO ownership handoff (deterministic lane contention)."""

    def __init__(self, clock: EventClock):
        self._clock = clock
        self._owner: Any = None  # _Frame, _ExternalWaiter, or thread ident
        self._waiters: list[Any] = []

    def acquire(self) -> None:
        if _current_frame() is not None:
            raise RuntimeError(
                "blocking acquire() inside a frame would deadlock the "
                "driver; yield ('acquire', lock) instead")
        clock = self._clock
        with clock._mutex:
            ident = threading.get_ident()
            if self._owner is None:
                self._owner = ident
                return
            waiter = _ExternalWaiter(clock._mutex)
            self._waiters.append(waiter)
            while not waiter.signalled:
                waiter.cond.wait()
            self._owner = ident

    def release(self) -> None:
        clock = self._clock
        with clock._mutex:
            if not self._waiters:
                self._owner = None
                return
            waiter = self._waiters.pop(0)
            self._owner = waiter
            if isinstance(waiter, _ExternalWaiter):
                waiter.signalled = True
                waiter.cond.notify()
            else:
                clock._make_ready(waiter)  # dispatched owning the lock

    def __enter__(self) -> "EventLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()


class EventEvent:
    """``threading.Event``-compatible flag. Frames wait via ``("wait", ev, timeout)`` effects; ``set`` wakes
    every waiter in FIFO order."""

    def __init__(self, clock: EventClock):
        self._clock = clock
        self._flag = False
        self._waiters: list[Any] = []

    def is_set(self) -> bool:
        return self._flag

    def set(self) -> None:
        clock = self._clock
        with clock._mutex:
            self._flag = True
            waiters, self._waiters = self._waiters, []
            for waiter in waiters:
                if isinstance(waiter, _ExternalWaiter):
                    waiter.signalled = True
                    waiter.cond.notify()
                else:
                    clock._make_ready(waiter)

    def wait(self, timeout: float | None = None) -> bool:
        if _current_frame() is not None:
            raise RuntimeError(
                "blocking wait() inside a frame would deadlock the "
                "driver; yield ('wait', event, timeout) instead")
        clock = self._clock
        with clock._mutex:
            if self._flag:
                return True
            waiter = _ExternalWaiter(clock._mutex)
            self._waiters.append(waiter)
            waiter.cond.wait(timeout)
            if waiter in self._waiters:
                self._waiters.remove(waiter)
            return self._flag
