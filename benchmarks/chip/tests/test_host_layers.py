"""The readers of the engine's host profile: on fake records, and on a tiny
GEMM cell run under a CPU profiler session."""
import gc
import json
from types import SimpleNamespace

import jax
import pytest

import harness
import host_layers
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LAYERS = {"compile_ms.job": "compile", "schedule_ms.job": "schedule",
          "dispatch_ms.job": "task_fn", "kv_host_ms.job": "kv", "walk_ms.job": "walk",
          "invoker_ms.job": "invoker", "loop_ms.job": "loop"}
NEW = list(LAYERS) + ["gc_ms.job", "frame_steps_per_job.job"]


def record(start_s, ms, steps):
    layers = dict.fromkeys(LAYERS.values(), 0)
    layers["kv"] = int(ms * 1e6)
    return SimpleNamespace(start_ns=int(start_s * 1e9), layers_ns=layers,
                           frame_steps=steps)


def fake_run(start=10.0, end=20.0):
    return SimpleNamespace(window=harness.Window(start, end, []))


LOG = SimpleNamespace(
    jobs=[record(9.5, 100.0, 1), record(10.0, 2.0, 10), record(15.0, 4.0, 20),
          record(20.0, 6.0, 30), record(20.5, 100.0, 1)],
    gc_pauses=[(9.9, 1.0), (10.5, 0.003), (19.9, 0.006), (20.1, 1.0)])


def test_only_jobs_that_start_in_the_window_count():
    run = fake_run()
    assert host_layers.layer_ms(run, "kv", LOG) == pytest.approx(4.0)
    assert host_layers.layer_ms(run, "walk", LOG) == 0.0
    assert host_layers.frame_steps(run, LOG) == pytest.approx(20.0)


def test_gc_pauses_outside_the_window_are_left_out():
    assert host_layers.gc_ms(fake_run(), LOG) == pytest.approx(1e3 * 0.009 / 3)


def test_no_record_in_the_window_reads_none():
    run = fake_run(30.0, 40.0)
    assert host_layers.layer_ms(run, "kv", LOG) is None
    assert host_layers.frame_steps(run, LOG) is None
    assert host_layers.gc_ms(run, LOG) is None
    empty = SimpleNamespace(jobs=[], gc_pauses=[(15.0, 1.0)])
    assert host_layers.gc_ms(fake_run(), empty) is None


def test_a_program_without_the_log_reads_none(monkeypatch):
    monkeypatch.setattr(host_layers, "host_log", lambda: None)
    for name in NEW:
        assert harness.load_reader(name).read(fake_run()) is None


def test_new_metrics_are_read_in_both_gemm_cells_only():
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["gemm_8192.b1024", "gemm_8192.b4096"]
            assert m["moves"] == "job_s"
    train = harness.resolve_cell(BENCH, "smollm_360m.train")
    assert not {m["name"] for m in train.per_layer} & set(NEW)


def test_a_traced_window_reports_every_new_metric(tmp_path):
    cell = harness.resolve_cell(BENCH, "gemm_8192.b1024")
    cell.config = dict(cell.config, n=256)
    cell.traffic = dict(cell.traffic, block=32)
    runner = harness.load_module("runners", "gemm_engine").Runner(cell, 2 ** 33 + 5)
    runner.setup()
    gc.collect()
    gc.freeze()   # as run.py does after set-up
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        window = harness.run_window(runner.job, 0.5)
    finally:
        jax.profiler.stop_trace()
    run = harness.Run(cell=cell, setup_s=0.0, window=window, peak={}, work=runner.work)
    specs = [m for m in cell.per_layer if m["name"] in NEW]
    got = harness.read_metrics(run, specs)
    assert set(got) == set(NEW)
    jobs = host_layers.window_jobs(run)
    assert len(jobs) == len(window.jobs)
    span_ms = sum(r.end_ns - r.start_ns for r in jobs) / len(jobs) / 1e6
    layers_ms = sum(got[m]["value"] for m in LAYERS)
    assert layers_ms == pytest.approx(span_ms, rel=0.03)
    assert len({r.frame_steps for r in jobs}) == 1
    assert len({job.info["tasks"] for job in window.jobs}) == 1
    assert got["gc_ms.job"]["value"] > 0
