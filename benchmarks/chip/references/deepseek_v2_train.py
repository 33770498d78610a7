"""Plain reference of DeepSeek-V2-Lite's training step on one chip's share, with AdamW.

Straight ``jax.numpy``, every product at ``HIGHEST`` in float32. Token
embedding; then the leading dense layers and the MoE layers, each an
RMSNorm, multi-head latent attention and a residual add, an RMSNorm, an
MLP and a residual add; a final RMSNorm and the untied output head.

- Latent attention: ``q = x W_q`` split per head into ``q_nope`` and
  ``q_pe``; ``[c_kv, k_pe] = x W_kva``, ``c_kv`` through an RMSNorm;
  ``[k_nope, v] = c_kv W_kvb`` per head; one rotary key ``k_pe`` shared by
  every head. Rotary positions in the rotate-half form on the rotary
  columns only, at YaRN's frequencies (computed here from the published
  ``rope_scaling``); softmax scale ``(qk_nope + qk_rope)^-0.5`` times
  YaRN's ``mscale(factor, mscale_all_dim)^2``. Causal.
- MoE layer: float32 router logits over all ``router_experts``, a
  softmax, the top ``num_experts_per_tok`` of it (not renormalised, times
  ``routed_scaling_factor``). Each held expert (``first_held_expert`` and
  the ``n_routed_experts`` after it) is a SwiGLU applied to every token of
  the block and weighted by that token's gate for it (0 where it was not
  chosen): no sorting, no capacity. Plus the shared experts' SwiGLU of
  width ``n_shared_experts x moe_intermediate_size``, once.
- Loss: mean cross-entropy, plus ``z_loss`` times the mean squared log-
  partition, plus for each MoE layer ``aux_loss_alpha`` times the mean
  over rows of ``sum_e f_e P_e`` over all ``router_experts`` (``f_e``:
  picks of ``e`` in the row over ``S k / E``; ``P_e``: ``e``'s mean score
  in the row).

A step takes its rows in blocks of ``ROWS`` and adds up their gradients,
and AdamW updates leaf by leaf in place, so that the reference fits the
chip once the program's state is freed. The initial weights are made
again at the end for the change, rather than kept.

``mode="fp8"`` is the control (``llama_train.product``). ``fault`` plants
one departure for ``control.py``: ``"capacity"`` (GShard's dispatch at a
capacity factor of 1.25 over groups of 2,048 tokens, dropping what
overflows), ``"renormalised"`` (the k weights renormalised, Mixtral's
rule), ``"no_yarn"`` (plain rotary frequencies and scale).

``c`` is the configuration file's ``model`` block (Hugging Face keys).
Parameters are a flat dict: ``embed, head, final_norm``, the leading dense
layers' leaves under ``lead_`` stacked on a leading axis, and the MoE
layers' leaves stacked on a leading axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from references.llama_train import (
    _frozen,
    _rmsnorm,
    diff_norms,
    leaf_norms,
    lr_at,
    product,
)

ROWS = 1                      # rows of a step taken at once
CAPACITY_FACTOR, CAPACITY_GROUP = 1.25, 2048
ATTN = ("norm1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2")

__all__ = ["shapes", "init_params", "batch", "readings", "leaf_norms", "diff_norms"]


def shapes(c: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """Leaf -> (shape, storage dtype, init std; 0 means ones)."""
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    qk, kvb = c["qk_nope_head_dim"] + rope, c["qk_nope_head_dim"] + c["v_head_dim"]
    F, f = c["intermediate_size"], c["moe_intermediate_size"]
    fs, n, E = c["n_shared_experts"] * f, c["n_routed_experts"], c["router_experts"]
    lead = c["first_k_dense_replace"]
    L = c["num_hidden_layers"] - lead
    w = c["torch_dtype"]

    def attn(k: int) -> dict:
        return {"norm1": ((k, d), "float32", 0.0),
                "wq": ((k, d, H * qk), w, d ** -0.5),
                "wkv_a": ((k, d, r + rope), w, d ** -0.5),
                "kv_norm": ((k, r), "float32", 0.0),
                "wkv_b": ((k, r, H * kvb), w, r ** -0.5),
                "wo": ((k, H * c["v_head_dim"], d), w, (H * c["v_head_dim"]) ** -0.5),
                "norm2": ((k, d), "float32", 0.0)}

    out = {"embed": ((V, d), w, c["initializer_range"]),
           "head": ((d, V), w, d ** -0.5),
           "final_norm": ((d,), "float32", 0.0)}
    out.update({f"lead_{k}": v for k, v in attn(lead).items()})
    out.update({"lead_w_gate": ((lead, d, F), w, d ** -0.5),
                "lead_w_up": ((lead, d, F), w, d ** -0.5),
                "lead_w_down": ((lead, F, d), w, F ** -0.5)})
    out.update(attn(L))
    out.update({"router": ((L, d, E), "float32", d ** -0.5),
                "e_gate": ((L, n, d, f), w, d ** -0.5),
                "e_up": ((L, n, d, f), w, d ** -0.5),
                "e_down": ((L, n, f, d), w, f ** -0.5),
                "s_gate": ((L, d, fs), w, d ** -0.5),
                "s_up": ((L, d, fs), w, d ** -0.5),
                "s_down": ((L, fs, d), w, fs ** -0.5)})
    return out


def _model(c: dict) -> tuple:
    """The model block as a hashable key (lists left out)."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
                        for k, v in c.items() if not isinstance(v, list)))


def _thaw(cf: tuple) -> dict:
    return {k: dict(v) if k == "rope_scaling" else v for k, v in cf}


@functools.lru_cache(maxsize=None)
def _init_fn(cf: tuple):
    c = _thaw(cf)

    @jax.jit
    def init(seed):
        root = jax.random.PRNGKey(seed)
        out = {}
        for i, (name, (shape, dtype, std)) in enumerate(shapes(c).items()):
            if std == 0.0:
                out[name] = jnp.ones(shape, dtype)
            else:
                x = jax.random.normal(jax.random.fold_in(root, i), shape, jnp.float32)
                out[name] = (x * std).astype(dtype)
        return out

    return init


def init_params(c: dict, seed: int) -> dict[str, jax.Array]:
    """The initial weights, on the device, in their storage types."""
    return _init_fn(_model(c))(seed)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _batch(batch_size: int, seq: int, vocab: int, seed):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch_size, seq + 1), 0, vocab,
                              dtype=jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def batch(c: dict, batch_size: int, seq: int, seed: int) -> dict[str, jax.Array]:
    """One step's rows: ``seq + 1`` tokens each, uniform over the chip's
    slice of the vocabulary, split into input and next token."""
    return _batch(batch_size, seq, c["vocab_size"], seed)


# --------------------------------------------------------------------------
# Forward and loss of a block of rows
# --------------------------------------------------------------------------

def yarn_inv_freq(c: dict, fault: str | None) -> jnp.ndarray:
    """YaRN's inverse frequencies of the rotary pairs (plain ones under
    ``no_yarn``)."""
    dim, theta, y = c["qk_rope_head_dim"], float(c["rope_theta"]), c["rope_scaling"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    if fault == "no_yarn":
        return extra

    def pair(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (2 * math.pi * rotations)) / (2 * math.log(theta))
    low = max(math.floor(pair(y["beta_fast"])), 0)
    high = min(math.ceil(pair(y["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y["factor"] * ramp + extra * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(c: dict, fault: str | None) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    y = c["rope_scaling"]
    if fault != "no_yarn" and y.get("mscale_all_dim"):
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, inv):
    """Rotate-half rotary embedding over (R, S, heads, dim)."""
    S, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(x, w, c, mm, fault):
    R, S, _ = x.shape
    H, eps = c["num_attention_heads"], c["rms_norm_eps"]
    nope, rope, r = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    vd = c["v_head_dim"]
    inv = yarn_inv_freq(c, fault)
    h = _rmsnorm(x, w["norm1"], eps)
    q = mm("bsd,de->bse", h, w["wq"]).reshape(R, S, H, nope + rope)
    kv_a = mm("bsd,de->bse", h, w["wkv_a"])
    c_kv = _rmsnorm(kv_a[..., :r], w["kv_norm"], eps)
    kv = mm("bsr,re->bse", c_kv, w["wkv_b"]).reshape(R, S, H, nope + vd)
    k_pe = _rope(kv_a[:, :, None, r:], inv)                     # one key, every head
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (R, S, H, rope))], axis=-1)
    s = mm("bshe,bthe->bhst", q, k) * softmax_scale(c, fault)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = mm("bhst,bthe->bshe", jax.nn.softmax(s, axis=-1), kv[..., nope:])
    return x + mm("bse,ed->bsd", a.reshape(R, S, H * vd), w["wo"])


def _swiglu(h, gate, up, down, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, gate)) * mm("td,df->tf", h, up),
              down)


def _gates(probs, c, fault):
    """(T, E) weight of each token for each expert: its top-k scores, 0
    elsewhere; under ``capacity``, 0 too past an expert's capacity."""
    T, E = probs.shape
    k = c["num_experts_per_tok"]
    top, chosen = jax.lax.top_k(probs, k)
    if fault == "renormalised" or c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)       # (T, k, E)
    if fault == "capacity":
        g = min(CAPACITY_GROUP, T)
        cap = max(1, int(k * g * CAPACITY_FACTOR / E))
        grouped = onehot.reshape(T // g, g * k, E)               # (token, slot) order
        place = (jnp.cumsum(grouped, axis=1) - grouped).reshape(T, k, E)
        onehot = onehot * (place < cap)
    return jnp.einsum("tk,tke->te", top, onehot), onehot


def _moe(x, w, c, mm, fault):
    R, S, d = x.shape
    T, E, k = R * S, c["router_experts"], c["num_experts_per_tok"]
    h = _rmsnorm(x, w["norm2"], c["rms_norm_eps"]).reshape(T, d)
    probs = jax.nn.softmax(mm("td,de->te", h, w["router"]), axis=-1)
    gates, onehot = _gates(probs, c, fault)
    picks = jnp.sum(jax.lax.stop_gradient(onehot), axis=1).reshape(R, S, E)
    f = jnp.sum(picks, axis=1) / (S * k / E)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs.reshape(R, S, E), axis=1), axis=-1))
    first = c["first_held_expert"]
    y = _swiglu(h, w["s_gate"], w["s_up"], w["s_down"], mm)
    for e in range(c["n_routed_experts"]):
        y = y + gates[:, first + e, None] * _swiglu(h, w["e_gate"][e], w["e_up"][e],
                                                    w["e_down"][e], mm)
    return x + y.reshape(R, S, d), c["aux_loss_alpha"] * aux


def _layer_params(p: dict, names, prefix: str, i: int) -> dict:
    return {n: p[prefix + n][i] for n in names}


def loss_fn(p: dict, tokens, labels, c: dict, mode: str, fault: str | None):
    """The loss of a block of rows; ``p`` in float32."""
    mm = product(mode)
    eps = c["rms_norm_eps"]
    x = p["embed"][tokens]
    aux = 0.0
    lead = c["first_k_dense_replace"]
    mlp = ("lead_w_gate", "lead_w_up", "lead_w_down")

    def dense(x, w):
        x = _attention(x, w, c, mm, fault)
        h = _rmsnorm(x, w["norm2"], eps)
        R, S, d = h.shape
        return x + _swiglu(h.reshape(R * S, d), w["lead_w_gate"], w["lead_w_up"],
                           w["lead_w_down"], mm).reshape(R, S, d)

    def moe(x, w):
        return _moe(_attention(x, w, c, mm, fault), w, c, mm, fault)

    # One layer's activations at a time: recomputed in the backward pass.
    for i in range(lead):
        w = _layer_params(p, ATTN, "lead_", i) | {n: p[n][i] for n in mlp}
        x = jax.checkpoint(dense)(x, w)
    for i in range(c["num_hidden_layers"] - lead):
        w = _layer_params(p, ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate",
                                     "s_up", "s_down"), "", i)
        x, a = jax.checkpoint(moe)(x, w)
        aux = aux + a
    x = _rmsnorm(x, p["final_norm"], eps)
    logits = mm("bsd,dv->bsv", x, p["head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + c["z_loss"] * jnp.mean(logz * logz) + aux


# --------------------------------------------------------------------------
# A step: gradients by blocks of rows, then AdamW leaf by leaf
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(1,))
def _accumulate(p, g, tokens, labels, share, cf: tuple, mode: str, fault):
    """``g`` plus this block's ``share`` of the step's gradient, and its
    share of the loss."""
    c = _thaw(cf)
    p32 = {n: x.astype(jnp.float32) for n, x in p.items()}
    loss, gb = jax.value_and_grad(loss_fn)(p32, tokens, labels, c, mode, fault)
    return loss * share, {n: g[n] + gb[n] * share for n in g}


@jax.jit
def _global_norm(g):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 1, 2))
def _adamw_leaf(p, mu, nu, g, scale, count, of: tuple):
    """One leaf's AdamW update; ``count`` is the 0-based step."""
    o = dict(of)
    g = g * scale
    n = (count + 1).astype(jnp.float32)
    mu = o["b1"] * mu + (1 - o["b1"]) * g
    nu = o["b2"] * nu + (1 - o["b2"]) * g * g
    upd = (mu / (1.0 - o["b1"] ** n)) / (jnp.sqrt(nu / (1.0 - o["b2"] ** n)) + o["eps"])
    p32 = p.astype(jnp.float32)
    if p.ndim >= 2:                 # decay: every leaf of rank 2 or more as stored
        upd = upd + o["weight_decay"] * p32
    return (p32 - lr_at(count, o) * upd).astype(p.dtype), mu, nu


def _step(p, mu, nu, count: int, data, cf, of, mode, fault):
    rows = data["tokens"].shape[0]
    blocks = max(1, rows // ROWS)
    g = {n: jnp.zeros(x.shape, jnp.float32) for n, x in p.items()}
    loss = 0.0
    for b in range(blocks):
        sl = slice(b * ROWS, (b + 1) * ROWS)
        lb, g = _accumulate(p, g, data["tokens"][sl], data["labels"][sl],
                            jnp.float32(1.0 / blocks), cf, mode, fault)
        loss = loss + lb
    scale = jnp.minimum(1.0, dict(of)["clip_norm"] / (_global_norm(g) + 1e-9))
    grad_norms = {n: v * scale for n, v in leaf_norms(g).items()}
    for n in list(p):
        p[n], mu[n], nu[n] = _adamw_leaf(p[n], mu[n], nu[n], g.pop(n), scale,
                                         jnp.int32(count), of)
    return p, mu, nu, loss, grad_norms


def readings(c: dict, o: dict, params: dict, batches: list[dict], mode: str = "f32",
             fault: str | None = None, seed: int | None = None) -> dict:
    """Losses of each step, the first step's (clipped) gradient norm per
    leaf, and each leaf's change after all steps, as host floats. The
    initial weights are ``params``; they are updated in place, so the
    change is taken against ``init_params(c, seed)`` made again."""
    p = dict(params)
    del params
    mu = {n: jnp.zeros(x.shape, jnp.float32) for n, x in p.items()}
    nu = {n: jnp.zeros(x.shape, jnp.float32) for n, x in p.items()}
    losses, first_grad = [], None
    cf, of = _model(c), _frozen(o)
    for count, data in enumerate(batches):
        p, mu, nu, loss, gn = _step(p, mu, nu, count, data, cf, of, mode, fault)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {n: float(v) for n, v in gn.items()}
    del mu, nu
    change = {n: float(v) for n, v in diff_norms(p, init_params(c, seed)).items()}
    return {"loss": losses, "grad": first_grad, "change": change}
