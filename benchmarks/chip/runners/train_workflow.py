"""A training job as a workflow DAG through ``WukongEngine``.

Each job is ``run_training_workflow`` over ``build_training_workflow``:
a chain of ``steps_per_job`` steps, each a data-shard task feeding a
train-step task, continuing from the previous job's final state. The step
is the program's ``build_train_step`` under ``jax.jit`` with parameters
and optimizer state donated, so one state lives on the chip at a time.
Step tasks return their metrics as device arrays: no host sync per step.

Configuration keys: ``model`` (Hugging Face ``config.json`` keys, plus
``z_loss`` and ``initializer_range``), ``program_config`` (the name the
program's registry gives the same model), ``optimizer``, ``reference``,
``limits``. Traffic keys: ``batch``, ``seq``, ``steps_per_job``.

The weights and every batch come from the reference module's
``init_params`` and ``batch``, from seeds derived from the run's seed, so
the reference can make them again. Set-up drives the very objects that the
window drives, through the window's own call: one job of ``steps_per_job``
steps from the seed. As its steps pass it reads each leaf's first gradient
(from AdamW's first moment after step 1) and each leaf's change after step
3, before the next step takes the state; the job's first three losses come
with its results. The reference follows those three steps.
"""
from __future__ import annotations

import math
from statistics import median

import jax

from harness import Check, derive_seed, load_module
import costs

CHECK_STEPS = 3
LAYER_LEAVES = {"norm1": ("norm1", "scale"), "wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
                "wv": ("mixer", "wv"), "wo": ("mixer", "wo"), "norm2": ("norm2", "scale"),
                "w_gate": ("mlp", "w_gate"), "w_up": ("mlp", "w_up"),
                "w_down": ("mlp", "w_down")}


def to_program(flat: dict) -> dict:
    """The reference's flat leaves in the program's parameter tree."""
    block: dict = {}
    for name, (group, leaf) in LAYER_LEAVES.items():
        block.setdefault(group, {})[leaf] = flat[name]
    return {"embed": flat["embed"], "blocks": [block],
            "final_norm": {"scale": flat["final_norm"]}}


def from_program(tree: dict) -> dict:
    block = tree["blocks"][0]
    flat = {name: block[g][leaf] for name, (g, leaf) in LAYER_LEAVES.items()}
    flat["embed"], flat["final_norm"] = tree["embed"], tree["final_norm"]["scale"]
    return flat


def check_program_config(cfg, m: dict) -> None:
    """The program's registry entry has the configuration's sizes."""
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"], "n_kv_heads": m["num_key_value_heads"],
            "d_ff": m["intermediate_size"], "vocab": m["vocab_size"],
            "rope_theta": m["rope_theta"], "norm_eps": m["rms_norm_eps"],
            "tie_embeddings": m["tie_word_embeddings"], "dtype": m["torch_dtype"],
            "block_pattern": ("attn+dense",), "activation": "swiglu",
            "hd": m["hidden_size"] // m["num_attention_heads"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg.name} departs from the configuration: "
                         f"{ {k: (got[k], want[k]) for k in want if got[k] != want[k]} }")


class Runner:
    def __init__(self, cell, seed: int) -> None:
        from repro.configs import get_config
        from repro.core import EngineConfig
        from repro.optim import AdamWConfig, adamw_init
        from repro.runtime.orchestrator import build_training_workflow, run_training_workflow
        from repro.runtime.train import build_train_step

        c, t = cell.config, cell.traffic
        self.m, self.o, self.limits = c["model"], c["optimizer"], c["limits"]
        self.batch_size, self.seq = t["batch"], t["seq"]
        self.steps_per_job = t["steps_per_job"]
        if self.steps_per_job < CHECK_STEPS:
            raise ValueError(f"a job of {self.steps_per_job} steps is shorter than the "
                             f"{CHECK_STEPS} the comparison reads")
        self.seed = seed
        self.ref = load_module("references", c["reference"])
        cfg = get_config(c["program_config"])
        check_program_config(cfg, self.m)
        opt = AdamWConfig(lr=self.o["lr"], b1=self.o["b1"], b2=self.o["b2"], eps=self.o["eps"],
                          weight_decay=self.o["weight_decay"], clip_norm=self.o["clip_norm"],
                          warmup=self.o["warmup"])
        self._adamw_init = adamw_init
        self._build, self._run = build_training_workflow, run_training_workflow
        self._engine_cfg = EngineConfig()
        self.jstep = jax.jit(build_train_step(cfg, opt), donate_argnums=(0, 1))
        self.state = None
        self.next_step = 0
        self._probe = None
        self.losses: list[jax.Array] = []
        self.program: dict = {}
        self._ref_readings: dict[str, dict] = {}
        tokens = self.batch_size * self.seq
        step_flops = costs.dense_lm_train_flops(self.m, self.batch_size, self.seq)
        self.work = {"steps": float(self.steps_per_job),
                     "tokens": float(tokens * self.steps_per_job),
                     "flops": step_flops * self.steps_per_job}

    def _weights_seed(self) -> int:
        return derive_seed(self.seed, "weights")

    def _batch(self, step: int) -> dict:
        return self.ref.batch(self.m, self.batch_size, self.seq,
                              derive_seed(self.seed, "batch", step))

    def _step(self, state, data):
        params, opt = state
        params, opt, metrics = self.jstep(params, opt, data)
        if self._probe is not None:
            self._probe(params, opt)
        return (params, opt), metrics

    def _job(self, n_steps: int) -> tuple[dict, list[str]]:
        first = self.next_step
        # The init task hands the state over and keeps no reference to it.
        held, self.state = [self.state], None
        dag, final_key, metric_keys = self._build(
            n_steps=n_steps, step_fn=self._step, init_fn=held.pop,
            data_fn=lambda i: self._batch(first + i))
        with jax.profiler.TraceAnnotation("bench/compute"):
            res = self._run(dag, final_key, metric_keys, self._engine_cfg)
        with jax.profiler.TraceAnnotation("bench/wait"):
            out = jax.block_until_ready(res.report.results)
        self.state = out[final_key]
        self.next_step += n_steps
        return out, metric_keys

    def setup(self) -> None:
        params = to_program(self.ref.init_params(self.m, self._weights_seed()))
        self.state = (params, self._adamw_init(params))
        del params
        readings: dict = {}
        step = 0

        def probe(params, opt) -> None:
            nonlocal step
            step += 1
            if step == 1:
                mu = from_program(opt["mu"])
                readings["grad"] = {n: float(v) / (1.0 - self.o["b1"])
                                    for n, v in self.ref.leaf_norms(mu).items()}
            elif step == CHECK_STEPS:
                jax.block_until_ready(params)
                p0 = self.ref.init_params(self.m, self._weights_seed())
                readings["change"] = {n: float(v) for n, v in
                                      self.ref.diff_norms(from_program(params), p0).items()}

        self._probe = probe
        out, keys = self._job(self.steps_per_job)
        self._probe = None
        readings["loss"] = [float(out[k]["loss"]) for k in keys[:CHECK_STEPS]]
        self.program = readings

    def job(self, k: int) -> dict:
        out, keys = self._job(self.steps_per_job)
        self.losses.extend(out[key]["loss"] for key in keys)
        return {}

    def _reference(self, mode: str) -> dict:
        if mode not in self._ref_readings:
            params = self.ref.init_params(self.m, self._weights_seed())
            batches = [self._batch(s) for s in range(CHECK_STEPS)]
            self._ref_readings[mode] = self.ref.readings(self.m, self.o, params, batches, mode)
        return self._ref_readings[mode]

    def compare(self, got: dict, ref: dict) -> list[Check]:
        """Loss per step, and by the worst leaf the first gradient's norm and
        the change's norm, each as a gap relative to the reference."""
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
        checks = [Check("loss_rel_gap", loss_gap, self.limits["loss_rel_gap"])]
        g_med = median(ref["grad"].values())
        # Leaves whose reference gradient is nought to rounding move by
        # round-off alone under AdamW: left out of the change.
        moving = [n for n, v in ref["grad"].items() if v >= 1e-3 * g_med]
        for key, names in (("grad", list(ref["grad"])), ("change", moving)):
            floor = median(ref[key][n] for n in names)
            gap = max(abs(got[key][n] - ref[key][n]) / max(ref[key][n], floor) for n in names)
            checks.append(Check(f"{key}_norm_gap", gap, self.limits[f"{key}_norm_gap"]))
        return checks

    def check(self) -> list[Check]:
        self.state = None           # the program's state goes before the reference runs
        finite = all(math.isfinite(float(x)) for x in self.losses)
        bad = [] if finite else [Check("window_loss_not_finite", 1.0, 0.0)]
        return bad + self.compare(self.program, self._reference("f32"))

    def control(self) -> list[Check]:
        return self.compare(self._reference("fp8"), self._reference("f32"))

    def faults(self) -> dict[str, list[Check]]:
        """Faults planted in the reference put in the program's place: half
        of each batch left out (the mean over the rest). A state left
        unchanged reads 1 by the change's measure and needs no run."""
        params = self.ref.init_params(self.m, self._weights_seed())
        half = self.batch_size // 2
        batches = [{n: v[:half] for n, v in self._batch(s).items()}
                   for s in range(CHECK_STEPS)]
        got = self.ref.readings(self.m, self.o, params, batches, "f32")
        return {"half_batch": self.compare(got, self._reference("f32"))}
