"""Multi-head latent attention with YaRN rotary scaling (DeepSeek-V2).

DeepSeek-V2 (arXiv:2405.04434 §2.1), without query compression, for
training and prefill. Per layer, for x of (B, S, d):

- ``q = x W_q``, per head ``[q_nope, q_pe]`` (``qk_nope_dim``, ``qk_rope_dim``);
- ``[c_kv, k_pe] = x W_kva``; ``c_kv`` goes through an RMSNorm;
- ``[k_nope, v] = c_kv W_kvb``, per head; ``k_pe`` is one rotary key
  shared by every head;
- rotary positions (rotate-half, YaRN's frequencies) on ``q_pe`` and
  ``k_pe`` only;
- causal attention over ``[q_nope, q_pe]`` and ``[k_nope, k_pe]`` at
  ``softmax_scale(cfg)``, then ``W_o`` over the heads' values.

Decoding through a latent cache is not implemented.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, YarnConfig
from repro.models.layers import (
    EMBED,
    HEADS,
    Params,
    _init,
    apply_rope,
    dtype_of,
    rmsnorm,
    sdpa,
)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(y: YarnConfig, dim: int, theta: float) -> tuple[int, int]:
    """The rotary pairs between which YaRN's ramp runs from the original
    frequencies (below ``low``) to the interpolated ones (above ``high``)."""
    def pair(rotations: float) -> float:
        return (dim * math.log(y.original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(y.beta_fast)), 0)
    high = min(math.ceil(pair(y.beta_slow)), dim - 1)
    return low, high


def rope_inv_freq(dim: int, theta: float, y: YarnConfig | None) -> jax.Array:
    """Inverse frequencies of ``dim // 2`` rotary pairs; YaRN's where given."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if y is None:
        return extra
    low, high = yarn_correction_range(y, dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: ModelConfig) -> float:
    scale = cfg.mla.qk_dim ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig) -> tuple[Params, Params]:
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    r = a.kv_lora_rank
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _init(ks[0], (d, H * a.qk_dim), d ** -0.5, dt),
        "wkv_a": _init(ks[1], (d, r + a.qk_rope_dim), d ** -0.5, dt),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "wkv_b": _init(ks[2], (r, H * (a.qk_nope_dim + a.v_dim)), r ** -0.5, dt),
        "wo": _init(ks[3], (H * a.v_dim, d), (H * a.v_dim) ** -0.5, dt),
    }
    s: Params = {"wq": (EMBED, HEADS), "wkv_a": (EMBED, None),
                 "kv_norm": {"scale": (None,)}, "wkv_b": (None, HEADS),
                 "wo": (HEADS, EMBED)}
    return p, s


def mla(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Causal latent attention over the whole sequence."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    nope, rope, r = a.qk_nope_dim, a.qk_rope_dim, a.kv_lora_rank
    with jax.named_scope("mla"):
        q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
        kv_a = x @ p["wkv_a"]
        c_kv = rmsnorm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
        kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + a.v_dim)
        pos = jnp.arange(S)
        inv_freq = rope_inv_freq(rope, cfg.rope_theta, cfg.yarn)
        q_pe = apply_rope(q[..., nope:], pos, cfg.rope_theta, inv_freq)
        k_pe = apply_rope(kv_a[..., None, r:], pos, cfg.rope_theta, inv_freq)
        y = cfg.yarn
        if y is not None:
            m = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
            if m != 1.0:            # cos and sin scaled by m
                q_pe, k_pe = q_pe * m, k_pe * m
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
        out = sdpa(q, k, kv[..., nope:], causal=True, scale=softmax_scale(cfg))
        return out.reshape(B, S, H * a.v_dim) @ p["wo"]
