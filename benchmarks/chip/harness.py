"""The benchmark's general machinery, free of any one cell.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
both are data files found by name (``configs/<name>.json``,
``traffic/<name>.json``). The configuration names its runner
(``runners/<name>.py``), which builds and submits jobs through the
system under test, and its plain reference (``references/<name>.py``).
Every metric is read by a reader found by its name (``metrics/<name>.py``,
or ``metrics/<stem>.py`` for ``<stem>.<suffix>``). Adding a
cell, a mix or a metric adds files; nothing here branches on a name.

Nothing in this module imports JAX, so it runs anywhere.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

HERE = Path(__file__).resolve().parent


# --------------------------------------------------------------------------
# Resolution by name
# --------------------------------------------------------------------------

def _load_path(path: Path, kind: str) -> ModuleType:
    mod_name = f"_chipbench_{kind}_{path.stem.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_module(kind: str, name: str) -> ModuleType:
    """Import ``<kind>/<name>.py`` (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return _load_path(path, kind)


def load_reader(name: str) -> ModuleType:
    """The reader of metric ``name``: ``metrics/<name>.py``, else the reader
    of the quantity the name starts with, ``metrics/<stem>.py`` for
    ``<stem>.<suffix>``. One quantity split by the end-to-end metric it
    moves (``device_idle.job``, ``device_idle.train``) keeps one reader."""
    for stem in (name, name.split(".", 1)[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load_path(path, "metrics")
    raise FileNotFoundError(f"no metric named {name!r}: metrics/{name}.py is missing")


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def resolve_cell(bench: dict, name: str) -> Cell:
    """The cell ``name`` of ``bench`` with its files and its metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((HERE.parents[1] / cfg_entry["file"]).read_text())
    traffic = load_json("traffic", w["traffic"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, w["chips"], w["config"], w["traffic"], config, traffic,
                e2e, per_layer)


# --------------------------------------------------------------------------
# Seeds
# --------------------------------------------------------------------------

def derive_seed(seed: int, *parts: Any) -> int:
    """A 31-bit seed from the run's seed and a path of names and indices.

    The run's seed may be any whole number; what the program and the
    reference get fits a signed 32-bit integer, which JAX's traced seeds
    need.
    """
    text = ":".join(str(p) for p in (seed,) + parts)
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:4], "big") & 0x7FFFFFFF


# --------------------------------------------------------------------------
# The measured window
# --------------------------------------------------------------------------

@dataclasses.dataclass
class JobRecord:
    index: int
    start: float
    end: float
    info: dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Window:
    start: float
    end: float
    jobs: list[JobRecord]

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_window(job: Callable[[int], dict], seconds: float,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Closed loop, one job in flight: submit, wait for every root, repeat.

    The window opens at the start of the first job and closes at the end
    of the first job that finishes after ``seconds`` have elapsed, so it
    holds whole jobs only. ``job(k)`` returns once job ``k``'s roots are
    on the device and ready, with a dict of what it counted.

    Each job ends with a full garbage collection, timed as part of it: the
    engine leaves a job's store (and with it the job's device blocks) in
    reference cycles, so a client that does not collect fills the chip's
    memory within some tens of 4096^2 jobs and its jobs fail. Between
    these, Python's collector runs at its defaults, and a collection falls
    inside the job that triggers it.
    """
    jobs: list[JobRecord] = []
    start = clock()
    k = 0
    while True:
        t0 = clock()
        info = job(k)
        gc.collect()
        t1 = clock()
        jobs.append(JobRecord(k, t0, t1, info))
        k += 1
        if t1 - start >= seconds:
            return Window(start, t1, jobs)


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    if len(values) == 1:
        return float(values[0])
    return statistics.quantiles(values, n=100, method="inclusive")[int(q) - 1]


# --------------------------------------------------------------------------
# What a metric reader is given
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: Cell
    setup_s: float
    window: Window
    peak: dict[str, float]
    work: dict[str, float]          # per job: e.g. flops, tokens, steps
    trace: Any = None               # trace.Reduced, in a traced run


def read_metrics(run: Run, specs: list[dict]) -> dict:
    """Each metric's reader, by name; a reader that finds nothing is left out."""
    out = {}
    for spec in specs:
        value = load_reader(spec["name"]).read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


# --------------------------------------------------------------------------
# Correctness: numbers, each beside its limit
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN compares false, so a NaN reading fails.
        return self.value <= self.limit


def checks_line(checks: list[Check]) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


class CompileMeter:
    """Counts JAX's tracing, lowering and compile events, and their seconds."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self.events = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_: object) -> None:
        if event in self.EVENTS:
            self.events += 1
            self.seconds += secs

    def on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
