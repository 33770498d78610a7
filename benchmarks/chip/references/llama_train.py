"""Plain reference of a Llama-style decoder's training step, with AdamW.

Straight ``jax.numpy``: token embedding, then per layer RMSNorm, grouped-
query attention with rotary positions (the rotate-half form of Hugging
Face's Llama), a residual add, RMSNorm, a SwiGLU MLP and a residual add;
a final RMSNorm and the tied embedding as the output head. The loss is
the mean cross-entropy plus ``z_loss`` times the mean squared log-
partition. Every product runs at ``HIGHEST`` precision in float32;
parameters are kept in the storage types the configuration states and
updated in float32 by AdamW as the configuration states it.

It also makes what the benchmark feeds the program: the initial weights
(``init_params``, one jitted call from the seed, in storage types) and
each step's token batch (``batch``). Nothing here comes from the program.

``mode="fp8"`` is the control: every operand of every product rounded to
float8 e4m3 with one scale per tensor, and every gradient that enters a
product rounded to float8 e5m2, the usual recipe one step below bfloat16.

``c`` is the configuration file's ``model`` block (Hugging Face keys).
Parameters are a flat dict of arrays, layers stacked on a leading axis:
``embed, norm1, wq, wk, wv, wo, norm2, w_gate, w_up, w_down, final_norm``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LEAVES = ("embed", "norm1", "wq", "wk", "wv", "wo", "norm2",
          "w_gate", "w_up", "w_down", "final_norm")


def shapes(c: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """Leaf -> (shape, storage dtype, init std; 0 means ones)."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    w = c["torch_dtype"]
    return {
        "embed": ((c["vocab_size"], d), w, c["initializer_range"]),
        "norm1": ((L, d), "float32", 0.0),
        "wq": ((L, d, H * hd), w, d ** -0.5),
        "wk": ((L, d, K * hd), w, d ** -0.5),
        "wv": ((L, d, K * hd), w, d ** -0.5),
        "wo": ((L, H * hd, d), w, (H * hd) ** -0.5),
        "norm2": ((L, d), "float32", 0.0),
        "w_gate": ((L, d, f), w, d ** -0.5),
        "w_up": ((L, d, f), w, d ** -0.5),
        "w_down": ((L, f, d), w, f ** -0.5),
        "final_norm": ((d,), "float32", 0.0),
    }


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items() if not isinstance(v, (dict, list))))


@functools.lru_cache(maxsize=None)
def _init_fn(cf: tuple):
    c = dict(cf)

    @jax.jit
    def init(seed):
        root = jax.random.PRNGKey(seed)
        out = {}
        for n, (name, (shape, dtype, std)) in enumerate(shapes(c).items()):
            if std == 0.0:
                out[name] = jnp.ones(shape, dtype)
            else:
                x = jax.random.normal(jax.random.fold_in(root, n), shape, jnp.float32)
                out[name] = (x * std).astype(dtype)
        return out

    return init


def init_params(c: dict, seed: int) -> dict[str, jax.Array]:
    """The initial weights, on the device, in their storage types."""
    return _init_fn(_frozen(c))(seed)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _batch(batch_size: int, seq: int, vocab: int, seed):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch_size, seq + 1), 0, vocab,
                              dtype=jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def batch(c: dict, batch_size: int, seq: int, seed: int) -> dict[str, jax.Array]:
    """One step's rows: ``seq + 1`` uniform tokens each, split into input and next token."""
    return _batch(batch_size, seq, c["vocab_size"], seed)


# --------------------------------------------------------------------------
# Products, at full precision or at the control's
# --------------------------------------------------------------------------

def _round(x, dtype):
    big = float(jnp.finfo(dtype).max)
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / big
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def _q_fwd(x):
    return _round(x, jnp.float8_e4m3fn)


_q_fwd.defvjp(lambda x: (_round(x, jnp.float8_e4m3fn), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(y):
    return y


_q_bwd.defvjp(lambda y: (y, None), lambda _, g: (_round(g, jnp.float8_e5m2),))


def product(mode: str):
    """``einsum`` at HIGHEST, or the control's float8 product."""
    if mode == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "fp8":
        return lambda spec, a, b: _q_bwd(
            jnp.einsum(spec, _q_fwd(a), _q_fwd(b), precision=HIGHEST))
    raise ValueError(mode)


# --------------------------------------------------------------------------
# Forward and loss
# --------------------------------------------------------------------------

def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding over (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def loss_fn(p: dict, tokens, labels, c: dict, mode: str):
    mm = product(mode)
    B, S = tokens.shape
    d, H, K = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = d // H, c["rms_norm_eps"]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        h = _rmsnorm(x, w["norm1"], eps)
        q = mm("bsd,de->bse", h, w["wq"]).reshape(B, S, H, hd)
        k = mm("bsd,de->bse", h, w["wk"]).reshape(B, S, K, hd)
        v = mm("bsd,de->bse", h, w["wv"]).reshape(B, S, K, hd)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        k = jnp.repeat(k, H // K, axis=2)   # query head h reads kv head h // (H/K)
        v = jnp.repeat(v, H // K, axis=2)
        s = mm("bshe,bthe->bhst", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        a = mm("bhst,bthe->bshe", jax.nn.softmax(s, axis=-1), v).reshape(B, S, H * hd)
        x = x + mm("bse,ed->bsd", a, w["wo"])
        h = _rmsnorm(x, w["norm2"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, w["w_gate"])) * mm("bsd,df->bsf", h, w["w_up"])
        return x + mm("bsf,fd->bsd", g, w["w_down"]), None

    stacked = {n: p[n] for n in LEAVES if n not in ("embed", "final_norm")}
    x = p["embed"][tokens]
    # One layer's activations at a time: recomputed in the backward pass.
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    x = _rmsnorm(x, p["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, p["embed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + c["z_loss"] * jnp.mean(logz * logz)


# --------------------------------------------------------------------------
# AdamW, as the configuration states it
# --------------------------------------------------------------------------

def lr_at(step, o: dict):
    """Learning rate of 0-based ``step``: linear warm-up, then cosine."""
    s = jnp.asarray(step, jnp.float32)
    warm = (s + 1.0) / max(1.0, o["warmup"])
    prog = jnp.clip((s - o["warmup"]) / max(1.0, o["total_steps"] - o["warmup"]), 0, 1)
    cos = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * jnp.where(s < o["warmup"], warm, cos)


def decayed(name: str, p: dict) -> bool:
    """Weight decay applies to every leaf of rank 2 or more as stored."""
    return p[name].ndim >= 2


@functools.partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(1, 2))
def _step(p, mu, nu, count, data, cf: tuple, of: tuple, mode: str):
    c, o = dict(cf), dict(of)
    p32 = {n: x.astype(jnp.float32) for n, x in p.items()}
    loss, g = jax.value_and_grad(loss_fn)(p32, data["tokens"], data["labels"], c, mode)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    g = {n: x * jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9)) for n, x in g.items()}
    lr = lr_at(count, o)
    count = count + 1
    b1c = 1.0 - o["b1"] ** count.astype(jnp.float32)
    b2c = 1.0 - o["b2"] ** count.astype(jnp.float32)
    new_p, new_mu, new_nu = {}, {}, {}
    for n in p:
        new_mu[n] = o["b1"] * mu[n] + (1 - o["b1"]) * g[n]
        new_nu[n] = o["b2"] * nu[n] + (1 - o["b2"]) * g[n] * g[n]
        upd = (new_mu[n] / b1c) / (jnp.sqrt(new_nu[n] / b2c) + o["eps"])
        if decayed(n, p):
            upd = upd + o["weight_decay"] * p32[n]
        new_p[n] = (p32[n] - lr * upd).astype(p[n].dtype)
    grad_norms = {n: jnp.linalg.norm(x.reshape(-1)) for n, x in g.items()}
    return new_p, new_mu, new_nu, count, loss, grad_norms


@jax.jit
def leaf_norms(tree: dict) -> dict[str, jax.Array]:
    return {n: jnp.linalg.norm(x.astype(jnp.float32).reshape(-1)) for n, x in tree.items()}


@jax.jit
def diff_norms(a: dict, b: dict) -> dict[str, jax.Array]:
    """Each leaf's ||a - b||, in float32."""
    return {n: jnp.linalg.norm((a[n].astype(jnp.float32) - b[n].astype(jnp.float32))
                               .reshape(-1)) for n in a}


def readings(c: dict, o: dict, params: dict, batches: list[dict], mode: str = "f32"
             ) -> dict:
    """Losses of each step, the first step's (clipped) gradient norm per
    leaf, and each leaf's change after all steps, as host floats."""
    p0 = params
    p = params
    mu = {n: jnp.zeros(x.shape, jnp.float32) for n, x in p.items()}
    nu = {n: jnp.zeros(x.shape, jnp.float32) for n, x in p.items()}
    count = jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    cf, of = _frozen(c), _frozen(o)
    for data in batches:
        p, mu, nu, count, loss, gn = _step(p, mu, nu, count, data, cf, of, mode)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {n: float(v) for n, v in gn.items()}
    change = {n: float(v) for n, v in diff_norms(p, p0).items()}
    return {"loss": losses, "grad": first_grad, "change": change}
