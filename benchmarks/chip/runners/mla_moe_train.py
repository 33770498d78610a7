"""A DeepSeek-V2 training job (latent attention, routed and shared experts)
as a workflow DAG through ``WukongEngine``.

The job, its set-up and its comparison are ``train_workflow``'s: a chain
of ``steps_per_job`` steps of the program's ``build_train_step`` under
``jax.jit``, state donated, continuing from the previous job's state. What
differs is the model: the program's registry entry is cut to the
configuration's share of the deployment (depth, held experts, vocabulary
slice) and checked against the file; the parameters map onto the
reference's leaves; the operations come from ``mla_moe_costs``.

After each job's wait (``bench/wait``) the runner reads the steps' MoE
counters, device scalars the step already returned, into the job's record:
``moe_held_rows`` (summed over the job's steps), ``moe_load_max`` (their
maximum) and ``moe_dropped`` (summed). No step waits for them. A dropped
assignment in any window step fails the run's comparison.
"""
from __future__ import annotations

import dataclasses

import jax

from harness import Check, load_module
import mla_moe_costs

train_workflow = load_module("runners", "train_workflow")
CHECK_STEPS = train_workflow.CHECK_STEPS

ATTN = {"norm1": ("norm1", "scale"), "wq": ("mixer", "wq"), "wkv_a": ("mixer", "wkv_a"),
        "kv_norm": ("mixer", "kv_norm", "scale"), "wkv_b": ("mixer", "wkv_b"),
        "wo": ("mixer", "wo"), "norm2": ("norm2", "scale")}
# Reference leaf -> path in the program's parameter tree.
LEAVES = {
    "embed": ("embed",), "head": ("lm_head",), "final_norm": ("final_norm", "scale"),
    **{f"lead_{n}": ("lead",) + path for n, path in ATTN.items()},
    "lead_w_gate": ("lead", "mlp", "w_gate"), "lead_w_up": ("lead", "mlp", "w_up"),
    "lead_w_down": ("lead", "mlp", "w_down"),
    **{n: ("blocks", 0) + path for n, path in ATTN.items()},
    "router": ("blocks", 0, "mlp", "router"),
    "e_gate": ("blocks", 0, "mlp", "w_gate"), "e_up": ("blocks", 0, "mlp", "w_up"),
    "e_down": ("blocks", 0, "mlp", "w_down"),
    "s_gate": ("blocks", 0, "mlp", "shared", "w_gate"),
    "s_up": ("blocks", 0, "mlp", "shared", "w_up"),
    "s_down": ("blocks", 0, "mlp", "shared", "w_down"),
}
COUNTERS = ("moe_held_rows", "moe_load_max", "moe_dropped")


def to_program(flat: dict) -> dict:
    """The reference's flat leaves in the program's parameter tree."""
    tree: dict = {"blocks": [{}]}
    for name, path in LEAVES.items():
        node = tree
        for key in path[:-1]:
            node = node[key] if isinstance(key, int) else node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def from_program(tree: dict) -> dict:
    flat = {}
    for name, path in LEAVES.items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat


# The configuration file's sections; its other top-level keys are the
# published model's, as the catalog gives them.
SECTIONS = ("name", "source", "reduced", "model", "deployment", "assumed", "precision",
            "program_config", "runner", "reference", "optimizer", "limits", "limits_why")


def model_of(config: dict) -> dict:
    """The model's keys: the published ones, under the program's own from
    the file's ``model`` block."""
    published = {k: v for k, v in config.items() if k not in SECTIONS}
    return {**published, **config["model"]}


def program_config(name: str, m: dict):
    """The program's registry entry, cut to the configuration's share, after
    checking that every published size agrees with the file."""
    from repro.configs import get_config

    cfg = get_config(name)
    y = m["rope_scaling"]
    want = {"d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"], "d_ff": m["intermediate_size"],
            "rope_theta": m["rope_theta"], "norm_eps": m["rms_norm_eps"],
            "tie_embeddings": m["tie_word_embeddings"], "dtype": m["torch_dtype"],
            "block_pattern": ("mla+moe",), "activation": "swiglu",
            "n_dense_lead": m["first_k_dense_replace"],
            "mla": (m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                    m["v_head_dim"]),
            "yarn": (y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
                     y["beta_slow"], y["mscale"], y["mscale_all_dim"]),
            "moe": (m["router_experts"], m["num_experts_per_tok"], "dropless",
                    m["moe_intermediate_size"], m["n_shared_experts"], m["norm_topk_prob"],
                    m["routed_scaling_factor"], m["aux_loss_alpha"])}
    moe, a, yc = cfg.moe, cfg.mla, cfg.yarn
    got = {k: getattr(cfg, k) for k in want if k not in ("mla", "yarn", "moe")}
    got["mla"] = (a.kv_lora_rank, a.qk_nope_dim, a.qk_rope_dim, a.v_dim) if a else None
    got["yarn"] = (yc.factor, yc.original_max_pos, yc.beta_fast, yc.beta_slow, yc.mscale,
                   yc.mscale_all_dim) if yc else None
    # The dropless rule neither renormalises the k weights nor scales them.
    got["moe"] = (moe.n_experts, moe.top_k, moe.dispatch, moe.d_expert, moe.n_shared,
                  False, 1, moe.aux_alpha) if moe else None
    if got != want:
        raise ValueError(f"program config {cfg.name} departs from the configuration: "
                         f"{ {k: (got[k], want[k]) for k in want if got[k] != want[k]} }")
    return dataclasses.replace(
        cfg, n_layers=m["num_hidden_layers"], vocab=m["vocab_size"],
        moe=dataclasses.replace(moe, first_held=m["first_held_expert"],
                                n_held=m["n_routed_experts"]))


class Runner(train_workflow.Runner):
    def __init__(self, cell, seed: int) -> None:
        from repro.core import EngineConfig
        from repro.optim import AdamWConfig, adamw_init
        from repro.runtime.orchestrator import build_training_workflow, run_training_workflow
        from repro.runtime.train import build_train_step

        c, t = cell.config, cell.traffic
        self.m, self.o, self.limits = model_of(c), c["optimizer"], c["limits"]
        self.batch_size, self.seq = t["batch"], t["seq"]
        self.steps_per_job = t["steps_per_job"]
        if self.steps_per_job < CHECK_STEPS:
            raise ValueError(f"a job of {self.steps_per_job} steps is shorter than the "
                             f"{CHECK_STEPS} the comparison reads")
        self.seed = seed
        self.ref = load_module("references", c["reference"])
        cfg = program_config(c["program_config"], self.m)
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
        self.dropped = 0.0
        self.program: dict = {}
        self._ref_readings: dict[str, dict] = {}
        tokens = self.batch_size * self.seq
        moe_layers = self.m["num_hidden_layers"] - self.m["first_k_dense_replace"]
        self.work = {
            "steps": float(self.steps_per_job),
            "tokens": float(tokens * self.steps_per_job),
            "flops": mla_moe_costs.mla_moe_train_flops(self.m, self.batch_size, self.seq)
            * self.steps_per_job,
            # Tokens through MoE layers in a job, and the rows one expert
            # gets in one layer's step when routing is even.
            "moe_layer_tokens": float(tokens * self.steps_per_job * moe_layers),
            "moe_rows_per_expert": float(tokens * self.m["num_experts_per_tok"]
                                         / self.m["router_experts"]),
        }

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
        counts = jax.device_get([[out[key][n] for n in COUNTERS] for key in keys])
        rows, load, dropped = zip(*counts)
        self.dropped += float(sum(dropped))
        return {"moe_held_rows": float(sum(rows)), "moe_load_max": float(max(load)),
                "moe_dropped": float(sum(dropped))}

    def check(self) -> list[Check]:
        """``train_workflow``'s comparison, and no assignment dropped in the
        window's steps."""
        return super().check() + [Check("moe_dropped", self.dropped, 0.0)]

    def _readings(self, batches: list[dict], mode: str, fault: str | None = None) -> dict:
        params = self.ref.init_params(self.m, self._weights_seed())
        return self.ref.readings(self.m, self.o, params, batches, mode, fault,
                                 self._weights_seed())

    def _reference(self, mode: str) -> dict:
        if mode not in self._ref_readings:
            batches = [self._batch(s) for s in range(CHECK_STEPS)]
            self._ref_readings[mode] = self._readings(batches, mode)
        return self._ref_readings[mode]

    def faults(self) -> dict:
        """Faults planted in the reference put in the program's place: half
        of each batch left out; GShard's dispatch at capacity 1.25, which
        drops assignments; the k weights renormalised; YaRN left out."""
        ref = self._reference("f32")
        batches = [self._batch(s) for s in range(CHECK_STEPS)]
        half = self.batch_size // 2
        out = {"half_batch": self.compare(
            self._readings([{n: v[:half] for n, v in b.items()} for b in batches], "f32"),
            ref)}
        for fault in ("capacity", "renormalised", "no_yarn"):
            out[fault] = self.compare(self._readings(batches, "f32", fault), ref)
        return out
