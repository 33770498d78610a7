"""DeepSeek-V2's latent attention and drop-free experts against the plain
reference (``benchmarks/chip/references/deepseek_v2_train.py``).

All on seeded random weights at a reduced size on the CPU, in float32 on
both sides: YaRN's frequencies and softmax scale, the MLA mixer, the MoE
layer cut into shares of its experts, routing under an imbalance that no
capacity would hold, the grouped product against ``jax.lax.ragged_dot``,
the balance loss, and whole AdamW steps through ``build_train_step``.
"""
import dataclasses
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops as kernel_ops
from repro.models import layers, mla
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.train import build_train_step

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
PUBLISHED = get_config("deepseek_v2_lite")

# The configuration file's model keys at a size the CPU runs in
# seconds: 1 dense layer and 2 MoE layers, top-6 over 16 experts.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "router_experts": 16,
    "n_routed_experts": 16, "first_held_expert": 0, "num_experts_per_tok": 6,
    "n_shared_experts": 2, "norm_topk_prob": False, "routed_scaling_factor": 1,
    "aux_loss_alpha": 0.001, "z_loss": 1e-4, "initializer_range": 0.02,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "torch_dtype": "float32",
}


@pytest.fixture(scope="module")
def chip():
    """The reference and the runner's map of its leaves, from the benchmark."""
    sys.path.insert(0, str(CHIP))
    try:
        import harness

        ref = harness.load_module("references", "deepseek_v2_train")
        runner = harness.load_module("runners", "mla_moe_train")
    finally:
        sys.path.remove(str(CHIP))
    return ref, runner


def program_cfg(c: dict, **moe):
    """The registry's DeepSeek-V2-Lite at ``c``'s sizes, in float32."""
    return dataclasses.replace(
        PUBLISHED, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], dtype="float32",
        mla=dataclasses.replace(PUBLISHED.mla, kv_lora_rank=c["kv_lora_rank"],
                                qk_nope_dim=c["qk_nope_head_dim"],
                                qk_rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"]),
        moe=dataclasses.replace(PUBLISHED.moe, n_experts=c["router_experts"],
                                d_expert=c["moe_intermediate_size"],
                                first_held=c["first_held_expert"],
                                n_held=c["n_routed_experts"], **moe))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# --------------------------------------------------------------------------
# YaRN and latent attention
# --------------------------------------------------------------------------

def test_yarn_range_frequencies_and_scale(chip):
    ref, _ = chip
    y, dim, theta = PUBLISHED.yarn, PUBLISHED.mla.qk_rope_dim, PUBLISHED.rope_theta
    assert mla.yarn_correction_range(y, dim, theta) == (10, 23)
    assert mla.softmax_scale(PUBLISHED) == pytest.approx(0.114721, abs=5e-7)
    i = np.arange(dim // 2, dtype=np.float64)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    got = mla.rope_inv_freq(dim, theta, y)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    c = dict(TINY, qk_rope_head_dim=64, qk_nope_head_dim=128)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(c, None)), want, rtol=1e-6)
    assert ref.softmax_scale(c, None) == pytest.approx(0.114721, abs=5e-7)


@pytest.mark.parametrize("yarn", [True, False], ids=["yarn", "plain_rope"])
def test_mla_matches_reference(chip, yarn):
    ref, _ = chip
    c = TINY
    cfg = program_cfg(c)
    if not yarn:
        cfg = dataclasses.replace(cfg, yarn=None)
    p, _ = mla.init_mla(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 48, c["hidden_size"]))
    ones = {"scale": jnp.ones((c["hidden_size"],))}
    got = x + mla.mla(p, layers.rmsnorm(ones, x, cfg.norm_eps), cfg)
    w = {"norm1": ones["scale"], "wq": p["wq"], "wkv_a": p["wkv_a"],
         "kv_norm": p["kv_norm"]["scale"], "wkv_b": p["wkv_b"], "wo": p["wo"]}
    want = ref._attention(x, w, c, ref.product("f32"), None if yarn else "no_yarn")
    assert rel(got, want) < 2e-5


def test_mla_refuses_decode():
    cfg = program_cfg(TINY)
    with pytest.raises(NotImplementedError, match="latent"):
        M.init_cache(cfg, 1, 16)


# --------------------------------------------------------------------------
# The MoE layer: shares, imbalance, balance loss
# --------------------------------------------------------------------------

def moe_weights(c: dict, seed: int) -> dict:
    """One MoE layer's reference leaves, all ``router_experts`` of them."""
    d, f, E = c["hidden_size"], c["moe_intermediate_size"], c["router_experts"]
    fs = c["n_shared_experts"] * f
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = jax.random.normal
    return {"norm2": jnp.ones((d,)), "router": n(ks[0], (d, E)) * d ** -0.5,
            "e_gate": n(ks[1], (E, d, f)) * d ** -0.5, "e_up": n(ks[2], (E, d, f)) * d ** -0.5,
            "e_down": n(ks[3], (E, f, d)) * f ** -0.5, "s_gate": n(ks[4], (d, fs)) * d ** -0.5,
            "s_up": n(ks[5], (d, fs)) * d ** -0.5, "s_down": n(ks[6], (fs, d)) * fs ** -0.5}


def program_moe(w: dict, c: dict, x, first: int, held: int):
    """The program's layer over experts ``first`` to ``first + held``, and
    its counters; ``x`` before the layer's norm."""
    cfg = program_cfg(dict(c, first_held_expert=first, n_routed_experts=held))
    sl = slice(first, first + held)
    p = {"router": w["router"], "w_gate": w["e_gate"][sl], "w_up": w["e_up"][sl],
         "w_down": w["e_down"][sl],
         "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"], "w_down": w["s_down"]}}
    h = layers.rmsnorm({"scale": w["norm2"]}, x, cfg.norm_eps)
    return layers.moe_dropless(p, h, cfg)


def test_shares_add_up_to_the_whole_layer(chip):
    """16 experts in 4 shares of 4: the shares' routed parts, the shared
    experts counted once, are the uncut reference layer."""
    ref, _ = chip
    c = TINY
    w = moe_weights(c, 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, c["hidden_size"]))
    h = layers.rmsnorm({"scale": w["norm2"]}, x, 1e-6)
    shared = layers.mlp({"w_gate": w["s_gate"], "w_up": w["s_up"], "w_down": w["s_down"]},
                        h, program_cfg(c))
    parts = [program_moe(w, c, x, 4 * s, 4) for s in range(4)]
    total = sum(y for y, _ in parts) - 3 * shared
    want, aux = ref._moe(x, w, c, ref.product("f32"), None)
    assert rel(x + total, want) < 2e-5
    for _, st in parts:
        assert float(st["moe_aux"]) == pytest.approx(float(aux), rel=1e-5)
        assert int(st["moe_dropped"]) == 0
    # Each assignment lands in exactly one share.
    assert sum(int(st["moe_held_rows"]) for _, st in parts) == 2 * 40 * 6


def test_no_assignment_dropped_under_imbalance(chip):
    """Every token's top choice is expert 0: its 80 rows are computed,
    where a capacity of 1.25 x even would hold 37 of them."""
    ref, _ = chip
    c = TINY
    w = moe_weights(c, 7)
    w["router"] = w["router"].at[:, 0].set(4.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2, 40, c["hidden_size"]))) + 0.5
    y, st = program_moe(w, c, x, 0, 4)
    sub = dict(c, n_routed_experts=4)
    want, _ = ref._moe(x, w, sub, ref.product("f32"), None)
    assert rel(x + y, want) < 2e-5
    assert int(st["moe_load_max"]) == 80 and int(st["moe_dropped"]) == 0
    dropped, _ = ref._moe(x, w, sub, ref.product("f32"), "capacity")
    assert rel(dropped, want) > 1e-2


@pytest.mark.parametrize("sizes", [[100, 0, 36, 50] + [10] * 12, [576] + [0] * 15],
                         ids=["uneven", "one_group"])
def test_grouped_matmul_matches_ragged_dot(sizes):
    """The kernel's grouped rows and its gradients on them, against
    ``jax.lax.ragged_dot``: an empty group, groups across its 512-row
    tiles, and every row in one group."""
    m, d, f = 1152, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    lhs = jax.random.normal(ks[0], (m, d))
    rhs = jax.random.normal(ks[1], (len(sizes), d, f))
    g = jax.random.normal(ks[2], (m, f))
    sizes = jnp.array(sizes, jnp.int32)
    rows = int(jnp.sum(sizes))

    def loss(prod):
        return lambda a, b: jnp.sum((prod(a, b, sizes) * g)[:rows])

    ragged = functools.partial(jax.lax.ragged_dot, preferred_element_type=jnp.float32)
    got = jax.value_and_grad(loss(kernel_ops.grouped_matmul), argnums=(0, 1))(lhs, rhs)
    want = jax.value_and_grad(loss(ragged), argnums=(0, 1))(lhs, rhs)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert rel(got[1][0][:rows], want[1][0][:rows]) < 1e-5
    assert rel(got[1][1], want[1][1]) < 1e-5


def test_balance_loss_matches_its_formula():
    c = TINY
    w = moe_weights(c, 9)
    B, S, E, k = 3, 20, c["router_experts"], c["num_experts_per_tok"]
    x = jax.random.normal(jax.random.PRNGKey(10), (B, S, c["hidden_size"]))
    _, st = program_moe(w, c, x, 0, 4)
    h = np.asarray(layers.rmsnorm({"scale": w["norm2"]}, x, 1e-6), np.float64)
    logits = h @ np.asarray(w["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[..., :k]
    loss = 0.0
    for b in range(B):
        f = np.bincount(top[b].ravel(), minlength=E) / (S * k / E)
        loss += np.sum(f * probs[b].mean(0))
    assert float(st["moe_aux"]) == pytest.approx(0.001 * loss / B, rel=1e-5)


# --------------------------------------------------------------------------
# Whole steps through the training path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("microbatches", [1, 2])
def test_train_steps_match_reference(chip, microbatches):
    """Three AdamW steps of the program against the reference: the losses,
    the first step's gradient norm of each leaf and each leaf's change.
    The cut holds 8 of 16 experts, from the fifth."""
    ref, runner = chip
    c = dict(TINY, n_routed_experts=8, first_held_expert=4)
    o = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
         "clip_norm": 1.0, "warmup": 1, "total_steps": 10000, "min_lr_frac": 0.1}
    cfg = program_cfg(c)
    step = jax.jit(build_train_step(
        cfg, AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                         warmup=o["warmup"]), n_microbatches=microbatches))
    batches = [ref.batch(c, 4, 32, 20 + s) for s in range(3)]
    params = runner.to_program(ref.init_params(c, 11))
    opt = adamw_init(params)
    losses = []
    for s, data in enumerate(batches):
        params, opt, m = step(params, opt, data)
        losses.append(float(m["loss"]))
        if s == 0:
            grad = {n: float(v) / (1 - o["b1"])
                    for n, v in ref.leaf_norms(runner.from_program(opt["mu"])).items()}
            assert int(m["moe_dropped"]) == 0
            assert 0 < int(m["moe_load_max"]) <= int(m["moe_held_rows"]) <= 2 * 4 * 32 * 6
            assert float(m["moe_aux"]) > 0
    change = ref.diff_norms(runner.from_program(params), ref.init_params(c, 11))
    want = ref.readings(c, o, ref.init_params(c, 11), batches, "f32", None, 11)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    for n in want["grad"]:
        assert grad[n] == pytest.approx(want["grad"][n], rel=1e-3, abs=1e-7), n
        assert float(change[n]) == pytest.approx(want["change"][n], rel=1e-3, abs=1e-7), n


def test_param_counts_of_published_and_cut():
    """15.7B parameters, 2.4B of them active (the model card's 15.7B-A2.4B
    counts the embedding in both); the cut of the benchmark's cell holds
    635 M, as the reference's leaves add up."""
    pc = PUBLISHED.param_counts()
    assert pc["total"] == pytest.approx(15.7e9, rel=0.01)
    assert pc["active"] + pc["embed"] == pytest.approx(2.4e9, rel=0.12)
    cut = dataclasses.replace(PUBLISHED, n_layers=6, vocab=12800,
                              moe=dataclasses.replace(PUBLISHED.moe, n_held=8))
    assert cut.param_counts()["total"] == pytest.approx(635.47e6, rel=1e-4)
    assert math.isclose(mla.yarn_mscale(40.0, 0.707), 0.1 * 0.707 * math.log(40) + 1)
