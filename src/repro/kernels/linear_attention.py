"""Pallas TPU chunkwise gated linear attention (the mLSTM inner kernel).

The xLSTM matrix-memory recurrence C_t = f_t C_{t-1} + i_t k_t v_t^T is
computed in its chunkwise-parallel form: per (batch, head), chunks are
processed sequentially (grid axis ``arbitrary``) carrying the (hd, hd)
state matrix and the (hd,) normalizer in VMEM scratch; within a chunk the
intra-chunk term is a decay-masked (chunk x chunk) attention — two MXU
matmuls — and the inter-chunk term is one (chunk, hd) x (hd, hd) matmul.
This is the TPU adaptation of the CUDA chunked-scan kernels (FlashLinear-
Attention / mLSTM): HBM traffic is O(S·hd) instead of the O(S·hd²) a
naive recurrence materialization would need, and all heavy math lands on
the MXU.

Matches ``repro.kernels.ref.mlstm_chunk_ref`` (zero initial state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(
    q_ref, k_ref, v_ref,        # (c, hd)
    f_ref, i_ref,               # (c, 1) log-forget, input gate
    o_ref,                      # (c, hd)
    C_scratch, n_scratch,       # (hd, hd), (1, hd)
    *, chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        C_scratch[...] = jnp.zeros_like(C_scratch)
        n_scratch[...] = jnp.zeros_like(n_scratch)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)             # (c, 1)
    ig = i_ref[...].astype(jnp.float32)            # (c, 1)

    # Mosaic lowers no cumsum: the prefix sums of the log-forget gates
    # are a lower-triangular matmul, taken once as a column and once as
    # a row. HIGHEST keeps them f32, since they are exponentiated.
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = mask.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    fcum = jax.lax.dot_general(
        tril, f, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)        # (c, 1)
    fcum_row = jax.lax.dot_general(
        f, tril, (((0,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)        # (1, c)
    ftot = jnp.sum(f, axis=0, keepdims=True)       # (1, 1)
    decay_q = jnp.exp(fcum)                        # (c, 1)

    C = C_scratch[...]
    nvec = n_scratch[...]                          # (1, hd)
    y_inter = jax.lax.dot_general(
        q * decay_q, C, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (c, hd)
    n_inter = jax.lax.dot_general(
        q * decay_q, nvec, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (c, 1)

    # The input gate of key j scales column j of the decay matrix; it is
    # applied to the keys' rows instead, which is the same product.
    D = jnp.where(mask, jnp.exp(fcum - fcum_row), 0.0)
    scores = jax.lax.dot_general(
        q, k * ig, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * D    # (c, c)
    y = y_inter + jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    nrm = n_inter + jnp.sum(scores, axis=1, keepdims=True)
    y = y / jnp.maximum(jnp.abs(nrm), 1.0)
    o_ref[...] = y.astype(o_ref.dtype)

    kd = k * (ig * jnp.exp(ftot - fcum))           # (c, hd)
    C_scratch[...] = jnp.exp(ftot) * C + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (hd, hd)
    n_scratch[...] = jnp.exp(ftot) * nvec + jnp.sum(kd, axis=0,
                                                    keepdims=True)


def mlstm_chunk(
    q: jax.Array,               # (B, S, H, hd) fp32
    k: jax.Array,
    v: jax.Array,
    log_f: jax.Array,           # (B, S, H)
    i_gate: jax.Array,          # (B, S, H)
    *,
    chunk: int = 64,
    interpret: bool,
) -> jax.Array:
    B, S, H, hd = q.shape
    assert S % chunk == 0
    qt = q.transpose(0, 2, 1, 3)                   # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ft = log_f.transpose(0, 2, 1)[..., None]       # (B, H, S, 1)
    it = i_gate.transpose(0, 2, 1)[..., None]

    grid = (B, H, S // chunk)
    spec_seq = pl.BlockSpec((None, None, chunk, hd),
                            lambda b, h, ci: (b, h, ci, 0))
    spec_gate = pl.BlockSpec((None, None, chunk, 1),
                             lambda b, h, ci: (b, h, ci, 0))
    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec_seq, spec_seq, spec_seq, spec_gate, spec_gate],
        out_specs=spec_seq,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, ft, it)
    return out.transpose(0, 2, 1, 3)
