"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the chips of one host. It resolves the cell of
``BENCHMARK.json`` by name, builds it from the seed (set-up: imports,
device, inputs and weights, compiles or compile-cache reads, warm-up),
then runs a closed loop of jobs, one in flight, for ``--seconds``, and
checks what those jobs produced against the plain reference. With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics; otherwise the end-to-end ones. The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the line's last key.

It exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for, and in a directory that lacks the system under
test (``src/repro``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Programs that compile in under this many seconds are still written to the
# persistent cache. JAX's default of 1 s recompiles the GEMM block programs
# in every run: 0.46 s longer set-up in gemm_8192.b1024 (PERF.md).
CACHE_MIN_COMPILE_S = 0.0


def process_age_s() -> float:
    """Seconds since this process started, before the first line ran."""
    try:
        start_ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
    except (OSError, ValueError, IndexError):
        return 0.0
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK")
               - (time.perf_counter() - T_START))


def fail(code: int, msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    age = process_age_s()
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(2, f"the system under test (src/repro) is not in {ROOT}")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(bench, args.workload)
    # The compile cache lives in the checkout, at a path that never moves.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax

    marks = {"jax_imported": age + time.perf_counter() - T_START}
    devices = jax.devices()
    marks["devices_up"] = age + time.perf_counter() - T_START
    if devices[0].platform != "tpu":
        fail(3, f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        fail(3, f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    run(args, cell, devices[0], len(devices), age, marks)


def run(args, cell, device, n_devices: int, age: float, marks: dict | None = None) -> None:
    import jax

    import costs
    import harness
    from repro.runtime.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", CACHE_MIN_COMPILE_S)
    # No eviction, whatever the environment asks: an evicting cache fails
    # every write once it meets an entry written without eviction.
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compile_cache()
    meter = harness.CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    jax.monitoring.register_event_listener(meter.on_event)
    peak = costs.peaks(device.device_kind)

    marks = dict(marks or {})
    runner = harness.load_module("runners", cell.config["runner"]).Runner(cell, args.seed)
    marks["runner_built"] = age + time.perf_counter() - T_START
    runner.setup()
    # What set-up built lives for the whole run: move it out of the
    # collector's reach, so each job's collection walks that job's objects.
    gc.collect()
    gc.freeze()
    setup_s = age + time.perf_counter() - T_START
    compiles0, compile_s0 = meter.events, meter.seconds

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        window = harness.run_window(runner.job, args.seconds)
    if trace_dir:
        jax.profiler.stop_trace()
    compiles = meter.events - compiles0
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use")

    reduced = None
    if trace_dir:
        import trace_reduce

        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        reduced = trace_reduce.reduce(trace_reduce.load_events(path))
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = runner.check()
    failed = sum(j.info.get("failed", 0) for j in window.jobs)
    if compiles:
        checks.append(harness.Check("compiles_in_window", float(compiles), 0.0))
    correct = failed == 0 and all(c.ok for c in checks)

    r = harness.Run(cell=cell, setup_s=setup_s, window=window, peak=peak,
                    work=runner.work, trace=reduced)
    specs = cell.per_layer if args.trace else cell.end_to_end
    metrics = harness.read_metrics(r, specs)
    slowest = sorted(window.jobs, key=lambda j: j.seconds)[-3:][::-1]
    print(json.dumps({"info": {
        "cell": cell.name, "seed": args.seed, "jobs": len(window.jobs),
        "window_s": window.seconds, "compiles_in_window": compiles,
        "compile_s_in_setup": compile_s0, "compile_cache_hits": meter.cache_hits,
        "setup_marks_s": marks,
        "job_s_min": min(j.seconds for j in window.jobs),
        "slowest_jobs": [[j.index, j.seconds] for j in slowest]}}), flush=True)

    dev = {"platform": device.platform, "kind": device.device_kind, "count": n_devices,
           "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": len(window.jobs), "failed": failed,
            "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"], dev["window_s"] = reduced.busy_s, reduced.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in reduced.top_ops],
                             "idle_gaps": [list(x) for x in reduced.idle_by_span]}
    line["checks"] = harness.checks_line(checks)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
