"""The trace reduction against hand-computed values."""
from pathlib import Path

import pytest

import costs
import trace_reduce as T

OPS = [("%fusion.1 = f32[8] fusion(...)", 10, 20),
       ("%add.2 = f32[8] add(...)", 15, 30),
       ("%copy-start = (f32[8]) copy-start(...)", 50, 60),
       ("%x = f32[8] fusion(...)", 95, 110),     # runs past the window's end
       ("%y = f32[8] fusion(...)", -5, 2)]       # began before its start
MODULES = [("jit_a", 8, 32), ("jit_b", 49, 61), ("jit_c", 95, 110)]
SPANS = [("bench/window", 0, 100),
         ("bench/job", 0, 45), ("bench/compute", 1, 40), ("bench/wait", 40, 45),
         ("bench/job", 45, 100), ("bench/compute", 46, 90), ("bench/wait", 90, 100)]


def events():
    return T.Events({"/device:TPU:0": list(OPS)}, list(MODULES), list(SPANS))


def test_union_clips_and_merges():
    got = T.union(((s, e) for _, s, e in OPS), 0, 100)
    assert got == [(0, 2), (10, 30), (50, 60), (95, 100)]


def test_gaps_are_the_complement():
    assert T.gaps([(0, 2), (10, 30), (50, 60), (95, 100)], 0, 100) == [
        (2, 10), (30, 50), (60, 95)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_leaf_spans_drop_containers():
    leaves = T.leaf_spans([s for s in SPANS if s[0] != "bench/window"])
    assert [s[0] for s in leaves] == ["bench/compute", "bench/wait"] * 2


def test_gaps_charged_to_what_the_host_did():
    got = T.attribute([(2, 10), (30, 50), (60, 95)], SPANS[1:])
    # [2,10] in compute; [30,50]: compute 10, wait 5, between jobs 1,
    # the next compute 4; [60,95]: compute 30, wait 5.
    assert got == {"bench/compute": 52, "bench/wait": 10, T.UNTRACED: 1}


def test_reduce():
    r = T.reduce(events())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(37e-9)
    assert r.idle_share == pytest.approx(0.63)
    assert r.module_s == pytest.approx({"jit_a": 24e-9, "jit_b": 12e-9})
    assert r.module_calls == {"jit_a": 1, "jit_b": 1}
    assert dict(r.top_ops) == pytest.approx(
        {"jit_a:add.2": 15e-9, "jit_a:fusion.1": 10e-9, "jit_b:copy-start": 10e-9})
    assert r.top_ops[0][0] == "jit_a:add.2"
    assert dict(r.idle_by_span) == pytest.approx(
        {"bench/compute": 52e-9, "bench/wait": 10e-9, T.UNTRACED: 1e-9})


def test_busy_is_a_mean_over_devices():
    ev = events()
    ev.ops["/device:TPU:1"] = [("%z = f32[8] fusion(...)", 0, 100)]
    assert T.reduce(ev).busy_s == pytest.approx((37e-9 + 100e-9) / 2)


def test_reduce_needs_one_window_and_a_device():
    ev = events()
    ev.spans.append(("bench/window", 0, 5))
    with pytest.raises(ValueError, match="one 'bench/window' span"):
        T.reduce(ev)
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce(T.Events({}, [], SPANS))


def test_names():
    assert T.module_name("jit__matmul(9911653781764463374)") == "jit__matmul"
    assert T.op_name("%fusion.28 = u32[1024]{0} fusion(), kind=kLoop") == "fusion.28"


# A trace recorded on one TPU v5e: ``run.py --workload gemm_8192.b4096
# --seconds 0.1 --trace 1``, six jobs. The expected values were counted by
# brute force, one boolean per nanosecond of the window.
RECORDED = Path(__file__).parent / "data" / "gemm_8192_b4096_6jobs.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return T.load_events(RECORDED)


def test_recorded_trace_planes_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert len(recorded.ops["/device:TPU:0"]) == 408
    assert {s[0] for s in recorded.spans} == {
        "bench/window", "bench/build", "bench/compute", "bench/wait"}


def test_recorded_trace_busy_and_idle(recorded):
    r = T.reduce(recorded)
    assert r.window_s == pytest.approx(0.105023919, abs=1e-9)
    assert r.busy_s == pytest.approx(0.071712568, abs=1e-9)
    assert dict(r.idle_by_span) == pytest.approx({
        "bench/compute": 0.024253802, "bench/wait": 0.005581608,
        "bench/build": 0.001092280, T.UNTRACED: 0.002383661}, abs=1e-9)


def test_recorded_trace_per_program(recorded):
    r = T.reduce(recorded)
    # Six jobs of a 2 x 2 grid: 8 input blocks, 8 products, 4 sums each.
    assert r.module_calls == {"jit__block": 48, "jit__matmul": 48, "jit__add": 24}
    assert r.module_s == pytest.approx({"jit__block": 0.026178602,
                                        "jit__matmul": 0.038295273,
                                        "jit__add": 0.007294192}, abs=1e-9)
    assert r.top_ops[0] == ("jit__matmul:fusion", pytest.approx(0.034551227, abs=1e-9))


def test_recorded_matmul_roofline(recorded):
    import harness

    r = T.reduce(recorded)
    run = harness.Run(cell=None, setup_s=0.0, window=None,
                      peak=costs.peaks("TPU v5 lite"), work={"block": 4096.0}, trace=r)
    share = harness.load_module("metrics", "matmul_roofline").read(run)
    # 48 calls, compute-bound: 2 * 4096^3 / 197e12 = 697.7 us each, over 38.3 ms.
    assert share == pytest.approx(100 * 48 * 2 * 4096 ** 3 / 197e12 / 0.038295273)
    assert 87.4 < share < 87.5
