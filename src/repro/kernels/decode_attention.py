"""Pallas TPU decode attention: one query token vs. a long KV cache.

Decode attention is memory-bound (every cache byte is read once per
step), so the kernel's job is to stream K/V tiles through VMEM at full
HBM bandwidth while keeping the flash accumulator in registers/VMEM.
Grid = (B, K_heads, num_kv_blocks) with the kv axis sequential; the G =
H/K query heads of a kv group are processed together as a (G, hd) tile —
MXU-friendly and it amortizes each K/V tile read across the whole group
(the GQA rationale).

``kv_len`` masks the unwritten cache tail, so the same kernel serves any
prefix length (the decode_32k / long_500k shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    kvlen_ref,                   # scalar prefetch: (B,) int32
    q_ref,                       # (G, hd)
    k_ref, v_ref,                # (bk, hd)
    o_ref,                       # (G, hd)
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, block_k: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    kv_len = kvlen_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[...].astype(jnp.float32)                 # (G, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (G, bk)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_scratch[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[...] = (acc_scratch[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,                # (B, H, hd)
    k_cache: jax.Array,          # (B, S, K, hd)
    v_cache: jax.Array,          # (B, S, K, hd)
    kv_len: jax.Array,           # (B,) int32
    *,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    B, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    assert H % K == 0
    G = H // K
    block_k = min(block_k, S)
    assert S % block_k == 0
    sm_scale = hd ** -0.5

    qg = q.reshape(B, K, G, hd)
    kt = k_cache.transpose(0, 2, 1, 3)     # (B, K, S, hd)
    vt = v_cache.transpose(0, 2, 1, 3)

    grid = (B, K, S // block_k)
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, G, hd),
                             lambda b, h, ki, *_: (b, h, 0, 0)),
                pl.BlockSpec((None, None, block_k, hd),
                             lambda b, h, ki, *_: (b, h, ki, 0)),
                pl.BlockSpec((None, None, block_k, hd),
                             lambda b, h, ki, *_: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, G, hd),
                                   lambda b, h, ki, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, hd)
