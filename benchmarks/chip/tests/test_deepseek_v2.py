"""The DeepSeek-V2-Lite cell's yardstick and its comparison on the CPU.

``mla_moe_costs`` against shapes counted by hand, and at a tiny size the
reference's control and its planted faults in the program's place: each
fails at least one of the cell's limits, where a sound run passes them.
"""
import json

import pytest

import harness
import mla_moe_costs
from conftest import CHIP
from test_rehearsal import BENCH, tiny_cell

CELL = "deepseek_v2_lite.train"


def model():
    config = json.loads((CHIP / "configs" / "deepseek_v2_lite.json").read_text())
    return harness.load_module("runners", "mla_moe_train").model_of(config)


def test_weights_a_token_meets_by_part():
    p = mla_moe_costs.mla_moe_params(model())
    # q 2048 x 16*192, kv-down 2048 x (512+64), kv-up 512 x 16*(128+128), o 16*128 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560
    expert = 3 * 2048 * 1408                    # SwiGLU of width 1408
    assert p["attention"] == 6 * attn
    assert p["dense"] == 3 * 2048 * 10944
    assert p["shared"] == 5 * 2 * expert
    assert p["router"] == 5 * 2048 * 64
    assert p["routed"] == 5 * 0.75 * expert     # 6 of 64 experts, 8 held: 0.75 a token
    assert p["head"] == 2048 * 12800
    assert p["matmul"] == 295_632_896


def test_train_flops_per_step():
    tokens = 2 * 4096
    weights = 6 * 295_632_896 * tokens
    scores_and_values = 6 * 6 * 16 * (192 + 128) * 4096 * tokens
    assert mla_moe_costs.mla_moe_train_flops(model(), 2, 4096) == weights + scores_and_values
    assert weights + scores_and_values == pytest.approx(2.0716e13, rel=1e-4)


@pytest.fixture(scope="module")
def readings():
    """A tiny cell's sound, control and fault readings, from one set-up."""
    with pytest.MonkeyPatch.context() as mp:
        cell = tiny_cell(CELL, mp)
        runner = harness.load_module("runners", cell.config["runner"]).Runner(cell, 2 ** 33 + 7)
        runner.setup()
        harness.run_window(runner.job, 0.2)
        return runner.check(), runner.control(), runner.faults()


def test_sound_run_passes(readings):
    assert all(c.ok for c in readings[0]), readings[0]


def test_control_fails(readings):
    assert not all(c.ok for c in readings[1]), readings[1]


@pytest.mark.parametrize("fault", ["half_batch", "capacity", "renormalised", "no_yarn"])
def test_planted_fault_fails(readings, fault):
    assert not all(c.ok for c in readings[2][fault]), readings[2][fault]


def test_cell_reports_the_moe_counters():
    cell = harness.resolve_cell(BENCH, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_load_peak", "moe_rows_per_token", "mfu.train", "device_idle.train"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "train_tokens_per_s"}


def test_program_keys_do_not_restate_published_ones():
    config = json.loads((CHIP / "configs" / "deepseek_v2_lite.json").read_text())
    runner = harness.load_module("runners", "mla_moe_train")
    published = set(config) - set(runner.SECTIONS)
    assert published and not published & set(config["model"])
    assert set(config["reduced"]) <= published
