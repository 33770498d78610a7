"""Transformer building blocks: norm, RoPE, GQA attention, dense/MoE MLP.

Everything is functional: ``init_*`` returns ``(params, specs)`` where
``specs`` mirrors the params pytree with tuples of *logical axis names*
(resolved to mesh axes by ``repro.runtime.sharding``). Layer ``apply``
functions are pure and jit/scan/shard_map friendly.

Full-sequence self-attention runs through the fused splash attention
kernel on the TPU where its blocks tile the sequence; everywhere else,
the CPU among them, through ``sdpa``, the pure-jnp oracle.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models.config import ModelConfig

Params = dict[str, Any]

# Logical axis names (see runtime/sharding.py for the mesh mapping)
VOCAB, EMBED, HEADS, KV, HD, FF, EXPERTS, LAYERS, INNER, STATE = (
    "vocab", "embed", "heads", "kv_heads", "head_dim", "ff", "experts",
    "layers", "inner", "state",
)


def dtype_of(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def init_rmsnorm(cfg: ModelConfig) -> tuple[Params, Params]:
    p = {"scale": jnp.ones((cfg.d_model,), dtype=jnp.float32)}
    s = {"scale": (EMBED,)}
    return p, s


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: jax.Array | None = None) -> jax.Array:
    """Rotate-half rotary embedding. x: (..., S, n, hd); positions:
    broadcastable to (..., S); ``inv_freq`` (hd/2,) in place of theta's."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False
                   ) -> tuple[Params, Params]:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p: Params = {
        "wq": _init(ks[0], (d, H * hd), scale, dt),
        "wk": _init(ks[1], (d, K * hd), scale, dt),
        "wv": _init(ks[2], (d, K * hd), scale, dt),
        "wo": _init(ks[3], (H * hd, d), (H * hd) ** -0.5, dt),
    }
    s: Params = {
        "wq": (EMBED, HEADS),
        "wk": (EMBED, KV),
        "wv": (EMBED, KV),
        "wo": (HEADS, EMBED),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype=dt)
        p["bk"] = jnp.zeros((K * hd,), dtype=dt)
        p["bv"] = jnp.zeros((K * hd,), dtype=dt)
        s["bq"], s["bk"], s["bv"] = (HEADS,), (KV,), (KV,)
    return p, s


def _project_qkv(p: Params, xq: jax.Array, xkv: jax.Array, cfg: ModelConfig):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (
        q.reshape(B, Sq, H, hd),
        k.reshape(B, Skv, K, hd),
        v.reshape(B, Skv, K, hd),
    )


def sdpa(
    q: jax.Array,                # (B, Sq, H, hd)
    k: jax.Array,                # (B, Skv, K, hd)
    v: jax.Array,                # (B, Skv, K, hd)
    *,
    causal: bool,
    window: int | None = None,
    q_offset: int | jax.Array = 0,
    kv_len: jax.Array | None = None,   # valid prefix length (decode)
    scale: float | None = None,        # None: hd ** -0.5
) -> jax.Array:
    """Grouped-query scaled-dot-product attention, pure-jnp oracle path.

    Computes in fp32 for the softmax, returns q.dtype. ``q_offset`` is the
    absolute position of q[0] (decode/prefill continuation). ``kv_len``
    masks the KV tail (preallocated decode caches). ``v`` may be narrower
    or wider than ``q`` and ``k``; the output takes its width.
    """
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (hd ** -0.5 if scale is None else scale)

    qpos = jnp.arange(Sq) + q_offset            # (Sq,)
    kpos = jnp.arange(Skv)                      # (Skv,)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


# Attention calls traced, by path: "fused", or "sdpa/<reason>" with the
# reason "backend", "cross" or "untiled". It counts traced call sites, not
# layers: a layer scan traces its body once, and remat may trace it again.
ATTENTION_PATHS: collections.Counter[str] = collections.Counter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _attention_path(S: int, cross: bool) -> str:
    if not _on_tpu():
        return "sdpa/backend"
    if cross:
        return "sdpa/cross"
    if kernel_ops.splash_block(S) is None:
        return "sdpa/untiled"
    return "fused"


def attention(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    positions: jax.Array | None = None,
    xkv: jax.Array | None = None,     # cross attention source
    use_rope: bool = True,
) -> jax.Array:
    """Full-sequence attention (training / prefill).

    Self-attention whose sequence the kernel's blocks tile runs fused on
    the TPU (``kernels.ops.splash_attention``, under the named scope
    ``attention_fused``); the rest through ``sdpa``. ``ATTENTION_PATHS``
    counts the choice as it is traced."""
    B, S, _ = x.shape
    cross = xkv is not None
    src = xkv if cross else x
    q, k, v = _project_qkv(p, x, src, cfg)
    if use_rope and not cross:
        pos = positions if positions is not None else jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    path = _attention_path(S, cross)
    ATTENTION_PATHS[path] += 1
    causal, window = causal and not cross, None if cross else cfg.sliding_window
    if path == "fused":
        with jax.named_scope("attention_fused"):
            out = kernel_ops.splash_attention(q, k, v, causal=causal, window=window,
                                              scale=q.shape[-1] ** -0.5)
    else:
        out = sdpa(q, k, v, causal=causal, window=window)
    return out.reshape(B, S, -1) @ p["wo"]


def attention_decode(
    p: Params,
    x: jax.Array,                # (B, 1, d)
    cache_k: jax.Array,          # (B, Smax, K, hd)
    cache_v: jax.Array,
    pos: jax.Array,              # scalar int32: index of the new token
    cfg: ModelConfig,
    *,
    use_rope: bool = True,
    rotating: bool = False,      # sliding-window rotating cache
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode step against a preallocated KV cache."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, x, cfg)
    if use_rope:
        posv = jnp.full((1,), pos)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    Smax = cache_k.shape[1]
    slot = jnp.where(jnp.asarray(rotating), pos % Smax, jnp.minimum(pos, Smax - 1))
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=1)
    if rotating:
        kv_len = jnp.minimum(pos + 1, Smax)
        out = sdpa(q, cache_k, cache_v, causal=False, kv_len=kv_len)
    else:
        out = sdpa(q, cache_k, cache_v, causal=False, kv_len=pos + 1)
    return out.reshape(B, 1, -1) @ p["wo"], cache_k, cache_v


def attention_cross_decode(
    p: Params, x: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
) -> jax.Array:
    """Cross-attention during decode: static encoder KV cache."""
    B = x.shape[0]
    H, K, hd = x.shape, None, None  # silence linters
    q = (x @ p["wq"]).reshape(B, 1, -1, cache_k.shape[-1])
    out = sdpa(q, cache_k, cache_v, causal=False)
    return out.reshape(B, 1, -1) @ p["wo"]


# --------------------------------------------------------------------------
# Dense MLP (SwiGLU / squared-ReLU / GELU)
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig) -> tuple[Params, Params]:
    d, f = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        p = {
            "w_gate": _init(ks[0], (d, f), d ** -0.5, dt),
            "w_up": _init(ks[1], (d, f), d ** -0.5, dt),
            "w_down": _init(ks[2], (f, d), f ** -0.5, dt),
        }
        s = {"w_gate": (EMBED, FF), "w_up": (EMBED, FF), "w_down": (FF, EMBED)}
    else:
        p = {
            "w_up": _init(ks[0], (d, f), d ** -0.5, dt),
            "w_down": _init(ks[1], (f, d), f ** -0.5, dt),
        }
        s = {"w_up": (EMBED, FF), "w_down": (FF, EMBED)}
    return p, s


def mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.activation == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    else:  # gelu
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# --------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-based GShard-style dispatch)
# --------------------------------------------------------------------------

MOE_GROUP = 2048          # tokens per dispatch group (bounds dispatch FLOPs)


def init_moe(key, cfg: ModelConfig) -> tuple[Params, Params]:
    """The router scores all ``n_experts``; only the held experts' weights
    are here, and the shared experts' as one SwiGLU of their summed width."""
    moe = cfg.moe
    assert moe is not None
    d, f, E = cfg.d_model, moe.d_expert or cfg.d_ff, moe.n_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, E), d ** -0.5, jnp.float32),
        "w_gate": _init(ks[1], (moe.held, d, f), d ** -0.5, dt),
        "w_up": _init(ks[2], (moe.held, d, f), d ** -0.5, dt),
        "w_down": _init(ks[3], (moe.held, f, d), f ** -0.5, dt),
    }
    s = {
        "router": (EMBED, None),
        "w_gate": (EXPERTS, EMBED, FF),
        "w_up": (EXPERTS, EMBED, FF),
        "w_down": (EXPERTS, FF, EMBED),
    }
    if moe.n_shared:
        p["shared"], s["shared"] = init_mlp(
            ks[4], dataclasses.replace(cfg, d_ff=moe.n_shared * f))
    return p, s


def moe_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Top-k MoE with capacity-based dispatch (GShard/Switch style).

    Tokens are processed in groups of MOE_GROUP so the one-hot dispatch
    einsum stays O(S·group·d) instead of O(S²·d). Overflow tokens beyond
    expert capacity are dropped (standard TPU practice; capacity factor
    1.25).
    """
    assert cfg.moe is not None
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    B, S, d = x.shape
    g = min(cfg.moe_group, S)
    assert S % g == 0, (S, g)
    n_groups = S // g
    xg = x.reshape(B * n_groups, g, d)
    cap = max(1, int(k * g * cfg.moe_capacity_factor / E))

    logits = (xg.astype(jnp.float32) @ p["router"])        # (G, g, E)
    weights, chosen = jax.lax.top_k(logits, k)             # (G, g, k)
    weights = jax.nn.softmax(weights, axis=-1)

    onehot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)  # (G, g, k, E)
    # position of each assignment within its expert's queue, counted over
    # the flattened (token, slot) order so no two assignments share a slot
    G_ = onehot.shape[0]
    flat = onehot.reshape(G_, g * k, E)
    pos_flat = jnp.cumsum(flat, axis=1) - flat
    pos_in_expert = jnp.einsum("gske,gske->gsk",
                               pos_flat.reshape(G_, g, k, E), onehot)
    keep = pos_in_expert < cap                              # (G, g, k)
    weights = weights * keep.astype(weights.dtype)

    cap_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), cap,
                                dtype=jnp.float32)          # (G, g, k, C)
    # dispatch: (G, g, k, E) x (G, g, k, C) -> (G, g, E, C)
    dispatch = jnp.einsum("gske,gskc->gsec", onehot,
                          cap_onehot * keep[..., None].astype(jnp.float32))
    combine = jnp.einsum("gsk,gske,gskc->gsec", weights, onehot, cap_onehot)

    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)  # (G,E,C,d)
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"]))
        h = h * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    else:
        h = jnp.square(jax.nn.relu(jnp.einsum("gecd,edf->gecf", xe, p["w_up"])))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])       # (G,E,C,d)
    yg = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    return yg.reshape(B, S, d)


# --------------------------------------------------------------------------
# Mixture of Experts without dropping (DeepSeek-V2 routing, held experts)
# --------------------------------------------------------------------------

def moe_dropless(p: Params, x: jax.Array, cfg: ModelConfig
                 ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """DeepSeek's routed and shared experts, with no assignment dropped.

    The router scores every token over all ``n_experts`` in float32, takes
    a softmax and then its top k (greedy) as the weights, neither
    renormalised nor scaled. This device holds experts ``first_held`` to
    ``first_held + held``: the assignments to them are sorted by expert and
    run through grouped products (``kernels.ops.grouped_matmul``), whatever their
    imbalance; the other assignments are another device's part and add
    nothing here. The shared experts' SwiGLU is added for every token.

    Returns the layer's output and its counters: ``moe_aux``, the
    sequence-level balance loss over all experts times ``aux_alpha``
    (DeepSeek-V2 §2.2.3; its gradient flows into the scores);
    ``moe_held_rows``, rows the held experts' products computed;
    ``moe_load_max``, the most rows any held expert got;
    ``moe_dropped``, held assignments left without a row (always 0).
    """
    moe = cfg.moe
    assert moe is not None and moe.dispatch == "dropless"
    E, k, n = moe.n_experts, moe.top_k, moe.held
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        # float32 products at full precision: the TPU's default would round
        # the router's operands to bfloat16.
        logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, k)                 # (T, k)
        # f_e: picks of e in the row over S k / E; P_e: e's mean score there.
        picks = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=1)
        f = jnp.sum(picks.reshape(B, S, E), axis=1) * (E / (S * k))
        share = jnp.mean(probs.reshape(B, S, E), axis=1)
        aux = moe.aux_alpha * jnp.mean(jnp.sum(f * share, axis=-1))
    with jax.named_scope("moe_experts"):
        local = chosen.reshape(T * k) - moe.first_held
        held = (local >= 0) & (local < n)
        # Held assignments first, by expert; the rest after them.
        order = jnp.argsort(jnp.where(held, local, n), stable=True)
        sizes = jnp.sum(jax.nn.one_hot(local, n, dtype=jnp.int32), axis=0)
        rows = jnp.sum(sizes)
        # Rows past the held ones belong to no group, and the grouped
        # product leaves them undefined: each product's operand and result
        # there is selected to zero before it meets any arithmetic, forward
        # and backward.
        valid = (jnp.arange(T * k) < rows)[:, None]

        def grouped(lhs, w):
            return jnp.where(valid, kernel_ops.grouped_matmul(lhs, w, sizes), 0.0)

        xs = jnp.where(valid, xt[order // k], 0)                   # (T k, d)
        h = jax.nn.silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
        ys = grouped(h.astype(x.dtype), p["w_down"])
        ys = ys * weights.reshape(T * k)[order][:, None]
        # Back to (token, slot) order, then the sum over each token's slots.
        inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        y = jnp.sum(ys[inv].reshape(T, k, d), axis=1).astype(x.dtype)
    if moe.n_shared:
        with jax.named_scope("moe_shared"):
            y = y + mlp(p["shared"], xt, cfg)
    stats = {"moe_aux": aux, "moe_held_rows": rows,
             "moe_load_max": jnp.max(sizes),
             "moe_dropped": jnp.sum(held.astype(jnp.int32)) - rows}
    return y.reshape(B, S, d), stats
