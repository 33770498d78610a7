"""From a profiler trace to device intervals, busy time and idle gaps.

The JAX profiler writes an ``.xplane.pb``. On a TPU its device plane is
``/device:TPU:<n>``, whose line ``XLA Modules`` holds one event per
program run (``jit__matmul(<hash>)``) and whose lines ``XLA Ops`` and
``Async XLA Ops`` hold the operations inside them. The host plane
``/host:CPU`` holds the harness's own spans (``bench/...``), written by
``jax.profiler.TraceAnnotation`` on the same clock. Times are in ns.

Busy time is the union of the operations' intervals inside the window
span; every gap in that union is idle, and is charged to the innermost
host spans it overlaps, so an idle share says what the host was doing.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Iterable

Interval = tuple[str, float, float]  # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
UNTRACED = "no bench span"


@dataclasses.dataclass
class Events:
    ops: dict[str, list[Interval]]      # device operations, by device plane
    modules: list[Interval]             # program runs (all devices), no hash
    spans: list[Interval]               # the harness's host spans


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> Events:
    """Read the device and host events that the reduction needs."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[str, list[Interval]] = {}
    modules: list[Interval] = []
    spans: list[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev_ops = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    dev_ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULE_LINE:
                    modules.extend((module_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Events(ops, modules, spans)


def union(intervals: Iterable[tuple[float, float]], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``intervals`` clipped to [lo, hi]."""
    out: list[tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The complement of a disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def leaf_spans(spans: list[Interval]) -> list[Interval]:
    """Spans that hold no other span (the innermost ones on one thread)."""
    ordered = sorted(spans, key=lambda s: (s[1], -s[2]))
    return [s for i, s in enumerate(ordered)
            if not (i + 1 < len(ordered) and ordered[i + 1][1] < s[2])]


def attribute(gap_list: list[tuple[float, float]], spans: list[Interval]
              ) -> dict[str, float]:
    """ns of the gaps by the leaf span they overlap; the rest is UNTRACED."""
    leaves = sorted(leaf_spans(spans), key=lambda s: s[1])
    out: dict[str, float] = collections.defaultdict(float)
    j = 0
    for gs, ge in gap_list:
        covered = 0.0
        while j < len(leaves) and leaves[j][2] <= gs:
            j += 1
        k = j
        while k < len(leaves) and leaves[k][1] < ge:
            name, s, e = leaves[k]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            k += 1
        if ge - gs - covered > 0:
            out[UNTRACED] += ge - gs - covered
    return dict(out)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over the devices traced
    module_s: dict[str, float]        # device seconds by program
    module_calls: dict[str, int]
    top_ops: list[tuple[str, float]]  # (program:op, device seconds), at most 10
    idle_by_span: list[tuple[str, float]]  # (host span, idle seconds), at most 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(ev: Events, window: str = WINDOW_SPAN, top: int = 10) -> Reduced:
    """Busy time, per-program time and idle gaps inside the ``window`` span."""
    wins = [s for s in ev.spans if s[0] == window]
    if len(wins) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(wins)}")
    _, lo, hi = wins[0]
    if not ev.ops:
        raise ValueError("the trace holds no device plane")
    per_device = [union(((s, e) for _, s, e in ops), lo, hi)
                  for _, ops in sorted(ev.ops.items())]
    busy_ns = sum(e - s for busy in per_device for s, e in busy) / len(per_device)
    all_ops = [o for _, ops in sorted(ev.ops.items()) for o in ops]

    module_ns: dict[str, float] = collections.defaultdict(float)
    calls: dict[str, int] = collections.defaultdict(int)
    for name, s, e in ev.modules:
        if lo <= s and e <= hi:
            module_ns[name] += e - s
            calls[name] += 1

    # Which program each op ran in: the module run whose interval holds it.
    mods = sorted((m for m in ev.modules if lo <= m[1] and m[2] <= hi), key=lambda m: m[1])
    op_ns: dict[str, float] = collections.defaultdict(float)
    j = 0
    for name, s, e in sorted((o for o in all_ops if lo <= o[1] and o[2] <= hi),
                             key=lambda o: o[1]):
        while j < len(mods) and mods[j][2] < s:
            j += 1
        owner = mods[j][0] if j < len(mods) and mods[j][1] <= s else "?"
        op_ns[f"{owner}:{op_name(name)}"] += e - s
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]

    spans = [s for s in ev.spans if s[0] != window and lo <= s[1] and s[2] <= hi]
    # Idle gaps of the first device, by what the host was doing.
    idle = attribute(gaps(per_device[0], lo, hi), spans)
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / 1e9,
        module_s={k: v / 1e9 for k, v in module_ns.items()},
        module_calls=dict(calls),
        top_ops=[(k, v / 1e9) for k, v in top_ops],
        idle_by_span=[(k, v / 1e9) for k, v in idle_top],
    )
