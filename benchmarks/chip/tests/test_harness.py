"""Resolution by name, the window rule, seeds and the metric readers."""
import gc
import json
import re
import weakref

import pytest

import harness
from conftest import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = harness.resolve_cell(BENCH, name)
    assert (CHIP / "runners" / f"{cell.config['runner']}.py").is_file()
    assert (CHIP / "references" / f"{cell.config['reference']}.py").is_file()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_reader(m["name"]).read), m["name"]
    for m in cell.per_layer:
        assert m["moves"] in e2e


def test_unknown_cell_and_file_are_errors():
    with pytest.raises(KeyError, match="no workload 'nope'"):
        harness.resolve_cell(BENCH, "nope")
    with pytest.raises(FileNotFoundError, match="no metric named 'nope'"):
        harness.load_module("metrics", "nope")
    with pytest.raises(FileNotFoundError, match="no metric named 'nope.job'"):
        harness.load_reader("nope.job")


def test_a_split_quantity_keeps_one_reader():
    assert harness.load_reader("device_idle.job") is harness.load_reader("device_idle.train")
    assert harness.load_reader("mfu.job") is harness.load_reader("mfu")
    assert harness.load_reader("job_s") is harness.load_module("metrics", "job_s")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_its_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    # A full check of 24 cells fits its 43,200 s.
    cells = 24
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180 + 1200 <= 43200


def test_derive_seed_fits_31_bits_and_differs():
    big = 2 ** 31 + 12345
    seeds = {harness.derive_seed(big, "job", k) for k in range(1000)}
    assert len(seeds) == 1000
    assert all(0 <= s < 2 ** 31 for s in seeds)
    assert harness.derive_seed(big, "job", 3) == harness.derive_seed(big, "job", 3)
    assert harness.derive_seed(big, "job", 3) != harness.derive_seed(big + 1, "job", 3)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_holds_whole_jobs_and_closes_after_the_first_late_finish():
    clock = FakeClock()

    def job(k):
        clock.t += 0.4 if k != 2 else 1.0
        return {"k": k}

    w = harness.run_window(job, 2.0, clock=clock)
    # 0.4, 0.4, 1.0 = 1.8 s; the fourth job ends at 2.2 s.
    assert [j.index for j in w.jobs] == [0, 1, 2, 3]
    assert w.seconds == pytest.approx(0.4 * 3 + 1.0)
    assert [j.seconds for j in w.jobs] == pytest.approx([0.4, 0.4, 1.0, 0.4])


def test_window_frees_each_jobs_reference_cycles_before_the_next():
    class Store:
        pass

    stores = []

    def job(k):
        # Every earlier job's store is gone by the time this one starts.
        assert all(ref() is None for ref in stores)
        s = Store()
        s.self = s
        stores.append(weakref.ref(s))
        return {}

    gc.disable()
    try:
        w = harness.run_window(job, 0.05)
    finally:
        gc.enable()
    assert len(w.jobs) >= 2
    assert all(ref() is None for ref in stores)


def test_percentile():
    assert harness.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert harness.percentile([3.0], 95) == 3.0


def fake_run(trace=None, **work):
    jobs = [harness.JobRecord(k, k * 0.5, k * 0.5 + 0.4,
                              {"tasks": 1088, "kv_ops": 3000 + k, "compute_s": 0.3})
            for k in range(4)]
    cell = harness.resolve_cell(BENCH, CELLS[0])
    return harness.Run(cell=cell, setup_s=12.5, window=harness.Window(0.0, 2.0, jobs),
                       peak={"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
                       work=work, trace=trace)


class FakeTrace:
    window_s = 2.0
    busy_s = 0.5
    idle_share = 0.75
    module_s = {"jit__matmul": 0.004}
    module_calls = {"jit__matmul": 100}


def read(name, run):
    return harness.load_reader(name).read(run)


def test_end_to_end_readers():
    r = fake_run(flops=1e12, tokens=8192.0)
    assert read("setup_s", r) == 12.5
    assert read("job_s", r) == pytest.approx(0.5)
    assert read("job_p95_s", r) == pytest.approx(0.4)
    assert read("train_tokens_per_s", r) == pytest.approx(4 * 8192 / 2.0)
    assert read("train_tokens_per_s", fake_run(flops=1.0)) is None


def test_per_layer_readers():
    r = fake_run(trace=FakeTrace(), flops=1e12, block=1024.0)
    assert read("tasks_per_job.job", r) == 1088
    assert read("kv_ops_per_job.job", r) == pytest.approx(3001.5)
    assert read("engine_host_ms.job", r) == pytest.approx(300.0)
    assert read("mfu.job", r) == pytest.approx(100 * 4e12 / 2.0 / 200e12)
    assert read("mfu.train", r) == pytest.approx(100 * 4e12 / 2.0 / 200e12)
    assert read("device_idle.job", r) == pytest.approx(75.0)
    # 100 calls of 1024^3 at 800 GB/s: 12,582,912 B each, 15.73 us, over 4 ms.
    assert read("matmul_roofline", r) == pytest.approx(100 * 100 * 12582912 / 800e9 / 0.004)


def test_trace_readers_are_silent_without_a_trace():
    r = fake_run(flops=1e12, block=1024.0)
    for name in ("matmul_roofline", "mfu.job", "mfu.train", "device_idle.job",
                 "device_idle.train"):
        assert read(name, r) is None


def test_read_metrics_leaves_out_what_finds_nothing():
    r = fake_run(flops=1e12)
    specs = [{"name": "job_s", "unit": "s"}, {"name": "matmul_roofline", "unit": "%"}]
    assert harness.read_metrics(r, specs) == {"job_s": {"value": 0.5, "unit": "s"}}


def test_check_fails_on_nan_and_over_the_limit():
    assert harness.Check("x", 0.1, 0.2).ok
    assert not harness.Check("x", 0.3, 0.2).ok
    assert not harness.Check("x", float("nan"), 0.2).ok
