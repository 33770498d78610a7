"""The reader of ``inlined_gemm_roofline``, on a made-up reduced trace."""
import json

import pytest

import harness
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


class Trace:
    def __init__(self, module_s, module_calls):
        self.module_s, self.module_calls = module_s, module_calls


def run_with(trace, **work):
    cell = harness.resolve_cell(BENCH, "gemm_8192.b1024")
    return harness.Run(cell=cell, setup_s=1.0, window=harness.Window(0.0, 2.0, []),
                       peak={"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
                       work=work, trace=trace)


def read(run):
    return harness.load_reader("inlined_gemm_roofline").read(run)


def test_reads_the_inlined_programs_time():
    # 64 runs of 8 products and 7 sums of 1024^2 blocks: 1.718e10 operations
    # (85.9 us at 200e12) and 17 blocks of 4 MiB (89.1 us at 800e9) each.
    t = Trace({"jit_inlined_matmul8_add7": 0.0128, "jit__block": 0.005},
              {"jit_inlined_matmul8_add7": 64, "jit__block": 128})
    least = 17 * 1024 ** 2 * 4 / 800e9
    assert read(run_with(t, block=1024.0)) == pytest.approx(100 * 64 * least / 0.0128)


def test_sums_programs_of_every_structure():
    t = Trace({"jit_inlined_matmul2_add1": 0.004, "jit_inlined_matmul8_add7": 0.004},
              {"jit_inlined_matmul2_add1": 2, "jit_inlined_matmul8_add7": 1})
    bs = 1024.0
    small = max((4 * bs ** 3 + bs ** 2) / 200e12, 5 * bs * bs * 4 / 800e9)
    large = max((16 * bs ** 3 + 7 * bs ** 2) / 200e12, 17 * bs * bs * 4 / 800e9)
    assert read(run_with(t, block=bs)) == pytest.approx(100 * (2 * small + large) / 0.008)


@pytest.mark.parametrize("trace,work", [
    (None, {"block": 1024.0}),
    (Trace({"jit__matmul": 0.004}, {"jit__matmul": 100}), {"block": 1024.0}),
    (Trace({"jit_inlined_matmul8_add7": 0.004}, {"jit_inlined_matmul8_add7": 1}), {}),
])
def test_silent_without_inlined_programs(trace, work):
    assert read(run_with(trace, **work)) is None
