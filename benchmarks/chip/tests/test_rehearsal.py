"""A whole run of each cell on the CPU at tiny sizes, sound and broken.

``run.run`` is driven past the look for a chip with a stand-in device. A
sound run reads ``correct``; each fault planted in the timed path (an
answer altered where it is produced, a partial product left out, a step
that returns its state unchanged, half of the batch left out) reads
``correct: false``.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

import harness
import run as run_py
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


class StandInDevice:
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


def tiny_cell(name, monkeypatch):
    cell = harness.resolve_cell(BENCH, name)
    if cell.config["runner"] == "gemm_engine":
        cell.config = dict(cell.config, n=256)
        # The grid of the real cell (8 x 8 or 2 x 2) at 1/32 of its side.
        cell.traffic = dict(cell.traffic, block=cell.traffic["block"] // 32)
        return cell
    # Narrower than this, bfloat16's rounding alone reads over the limits
    # set at the published widths.
    small = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 1024}
    cell.config = dict(cell.config, model=dict(cell.config["model"], **small))
    cell.traffic = dict(cell.traffic, batch=4, seq=64, steps_per_job=3)
    import repro.configs

    program = dataclasses.replace(repro.configs.get_config(cell.config["program_config"]),
                                  n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                                  d_ff=256, vocab=1024)
    monkeypatch.setattr(repro.configs, "get_config", lambda name: program)
    return cell


def run_cell(name, monkeypatch, capsys, seconds=0.5, seed=2 ** 33 + 7):
    cell = tiny_cell(name, monkeypatch)
    args = run_py.parse_args(["--workload", name, "--seed", str(seed),
                              "--seconds", str(seconds), "--trace", "0"])
    run_py.run(args, cell, StandInDevice(), 1, 0.0)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch, capsys):
    line = run_cell(name, monkeypatch, capsys)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    names = {m["name"] for m in harness.resolve_cell(BENCH, name).end_to_end}
    assert set(line["metrics"]) == names
    assert all(m["value"] > 0 for m in line["metrics"].values())


GEMM = [n for n in CELLS if n.startswith("gemm")]
TRAIN = [n for n in CELLS if not n.startswith("gemm")]


@pytest.mark.parametrize("name", GEMM)
@pytest.mark.parametrize("fault", ["answer_altered", "partial_left_out"])
def test_gemm_fault_is_not_correct(name, fault, monkeypatch, capsys):
    import repro.apps.gemm as gemm

    if fault == "answer_altered":
        monkeypatch.setattr(gemm, "_matmul", jax.jit(lambda a, b: jnp.dot(a, b) * 1.05))
    else:
        monkeypatch.setattr(gemm, "_add", jax.jit(lambda a, b: a))
    line = run_cell(name, monkeypatch, capsys)
    assert not line["correct"]


def broken_step(fault):
    import repro.runtime.train as train

    real = train.build_train_step

    def build(cfg, opt, *a, **k):
        step = real(cfg, opt, *a, **k)

        def faulty(params, opt_state, batch):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return step(params, opt_state, {n: v[:half] for n, v in batch.items()})
            new_p, new_o, m = step(params, opt_state, batch)
            if fault == "state_unchanged":
                return params, opt_state, m
            return new_p, new_o, dict(m, loss=m["loss"] * 1.02)   # answer altered
        return faulty
    return build


@pytest.mark.parametrize("name", TRAIN)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_train_fault_is_not_correct(name, fault, monkeypatch, capsys):
    import repro.runtime.train as train

    monkeypatch.setattr(train, "build_train_step", broken_step(fault))
    line = run_cell(name, monkeypatch, capsys)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_fault_in_long_jobs_only_is_not_correct(name, monkeypatch, capsys):
    """A workflow that goes wrong only in jobs longer than two steps, as a
    path taken for the window's long jobs would: the comparison reads a job
    of the window's own length."""
    import repro.runtime.orchestrator as orchestrator

    real = orchestrator.build_training_workflow

    def build(n_steps, step_fn, **kw):
        def altered(state, data):
            new, m = step_fn(state, data)
            return new, dict(m, loss=m["loss"] * 1.02)
        return real(n_steps=n_steps, step_fn=altered if n_steps > 2 else step_fn, **kw)

    monkeypatch.setattr(orchestrator, "build_training_workflow", build)
    line = run_cell(name, monkeypatch, capsys)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name, monkeypatch):
    """The reference one precision step down, in the program's place, is
    not correct: it fails at least one of the cell's numbers."""
    cell = tiny_cell(name, monkeypatch)
    job_runner = harness.load_module("runners", cell.config["runner"]).Runner(cell, 2 ** 33 + 11)
    job_runner.setup()
    harness.run_window(job_runner.job, 0.2)
    assert all(c.ok for c in job_runner.check())
    assert not all(c.ok for c in job_runner.control())


def test_run_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        run_py.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    assert "needs a TPU" in capsys.readouterr().err

