"""Pallas TPU flash attention (causal / sliding-window, GQA).

Online-softmax attention tiled for VMEM: grid = (B, H, num_q_blocks,
num_kv_blocks) with the kv axis marked ``arbitrary`` (sequential) so the
running (max, sum, acc) state lives in VMEM scratch across kv steps.
Block shapes default to (128, 128) — MXU-aligned (multiples of the
128-lane systolic dimension) and small enough that q/k/v tiles + fp32
accumulator fit comfortably in the ~16 MB of VMEM:
  qb·hd(bf16) + 2·kb·hd(bf16) + qb·kb(fp32) + qb·hd(fp32) ≈ 260 KB.

Causal and sliding-window masks are applied per-tile from absolute row /
column indices; fully-masked kv tiles are skipped with ``@pl.when`` (the
TPU grid is executed in order, so for causal attention the skipped tail
costs only the (empty) grid step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,        # (bq, hd), (bk, hd), (bk, hd)
    o_ref,                      # (bq, hd)
    m_scratch, l_scratch, acc_scratch,
    *, causal: bool, window: int | None, sm_scale: float,
    block_q: int, block_k: int, kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    # tile visibility test (causal: skip tiles strictly above the diagonal;
    # windowed: also skip tiles entirely older than the window)
    run = True
    if causal:
        run = jnp.asarray(k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)

    @pl.when(run if not isinstance(run, bool) else True)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = cols < kv_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # (bq, bk)
        l_new = alpha * l_scratch[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[...] = (acc_scratch[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                 # (B, S, H, hd)
    k: jax.Array,                 # (B, S, K, hd)
    v: jax.Array,                 # (B, S, K, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    B, S, H, hd = q.shape
    K = k.shape[2]
    assert H % K == 0
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    sm_scale = hd ** -0.5

    # layout: one (b, h) pair per grid row; kv head = h // G
    qt = q.transpose(0, 2, 1, 3)              # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)              # (B, K, S, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, S // block_q, S // block_k)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_len=S,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),   # fp32 accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
