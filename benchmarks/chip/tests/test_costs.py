"""Operations, bytes and peaks, against hand-computed values."""
import json

import pytest

import costs
from conftest import CHIP


def test_peaks_of_a_v5e():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind 'TPU v9'"):
        costs.peaks("TPU v9")


def test_matmul_cost_and_which_bound():
    flops, nbytes = costs.matmul_cost(1024, 1024, 1024, 4, 4)
    assert flops == 2 * 1024 ** 3
    assert nbytes == 3 * 1024 * 1024 * 4
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(12582912 / 819e9)
    flops, nbytes = costs.matmul_cost(4096, 4096, 4096, 4, 4)
    t, bound = costs.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute" and t == pytest.approx(2 * 4096 ** 3 / 197e12)


def test_gemm_job_flops():
    assert costs.gemm_job_flops(8192) == 2 * 8192 ** 3


def smollm():
    return json.loads((CHIP / "configs" / "smollm_360m.json").read_text())["model"]


def test_smollm_parameter_count():
    p = costs.dense_lm_params(smollm())
    # q 960x960, k and v 960x320, o 960x960, gate/up/down 960x2560: 9,830,400 a layer.
    assert p["layers"] == 32 * 9_830_400
    assert p["embed"] == 49152 * 960
    assert p["head"] == 0                       # tied to the embedding
    assert p["norms"] == 32 * 2 * 960 + 960
    assert p["total"] == 361_821_120            # SmolLM-360M's published size
    assert p["matmul"] == 32 * 9_830_400 + 49152 * 960


def test_smollm_train_flops_per_step():
    tokens = 4 * 2048
    six_nd = 6 * (32 * 9_830_400 + 49152 * 960) * tokens
    attention = 12 * 32 * 15 * 64 * 2048 * tokens
    assert costs.dense_lm_train_flops(smollm(), 4, 2048) == six_nd + attention
    assert six_nd == pytest.approx(1.7781e13, rel=1e-4)
