"""Jitted public wrappers for the Pallas kernels.

On the TPU they run compiled. On the CPU backend, where the test suite
runs, they run in Pallas interpret mode, validated against ``ref.py``
(megablox's grouped product against ``jax.lax.ragged_dot``, splash
attention against ``layers.sdpa``); any other backend is an error. The
wrappers choose block sizes and handle layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as _splash
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from repro.kernels import decode_attention as _dec
from repro.kernels import linear_attention as _la


def interpret_mode() -> bool:
    """Whether the kernels must run in interpret mode on this backend.

    Decided when a wrapper is traced, not at import: ``tpu`` compiles
    the kernels, ``cpu`` interprets them, and any other backend raises
    rather than dropping into the interpreter unannounced."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, kv_len, *, block_k=512):
    return _dec.decode_attention(q, k_cache, v_cache, kv_len,
                                 block_k=min(block_k, k_cache.shape[1]),
                                 interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_chunk(q, k, v, log_f, i_gate, *, chunk=64):
    return _la.mlstm_chunk(q, k, v, log_f, i_gate,
                           chunk=min(chunk, q.shape[1]),
                           interpret=interpret_mode())


# Tiles of the grouped product: 512 rows, and at most 512 x 1408 of the
# weight a step (a larger tile overflows the v5e's VMEM in the backward
# pass).
GMM_ROWS, GMM_WIDE, GMM_NARROW = 512, 1408, 512


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs``'s rows in consecutive groups, group g's times ``rhs[g]``,
    in float32: megablox's grouped product, with its own backward pass.

    Only the grouped rows are computed: rows past ``sum(group_sizes)``
    come out undefined, and so does their gradient."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tk, tn = (min(k, GMM_NARROW), min(n, GMM_WIDE)) if k >= n else \
        (min(k, GMM_WIDE), min(n, GMM_NARROW))
    return _gmm(lhs, rhs, group_sizes, jnp.float32, (math.gcd(m, GMM_ROWS), tk, tn),
                None, None, False, interpret_mode())


# Query and key blocks of the fused attention kernel, in order of
# preference: the first that divides the sequence is taken. At S 2048 on a
# v5e, 1024 ran one layer's forward and backward fastest (PERF.md).
SPLASH_BLOCKS = (1024, 512, 256, 128)


def splash_block(seq: int) -> int | None:
    """The fused attention kernel's block for a sequence of ``seq``, or
    None where no block tiles it."""
    return next((b for b in SPLASH_BLOCKS if seq % b == 0), None)


@functools.lru_cache(maxsize=64)
def _splash_kernel(seq: int, heads: int, causal: bool, window: int | None,
                   block: int, interpret: bool):
    """Splash attention over ``heads`` query heads of ``seq`` positions.

    The mask is ``layers.sdpa``'s: key ``t`` is seen from query ``s``
    where ``t <= s`` (causal) and ``t > s - window`` (a window). Its
    block structure is computed once here, as concrete arrays, so that
    tracing a step does not compute it again for every layer. The
    backward pass is one kernel, which computes dq beside dk and dv."""
    shape = (seq, seq)
    if window is not None:
        mask = _splash.LocalMask(shape, (window - 1, 0 if causal else None), 0)
    elif causal:
        mask = _splash.CausalMask(shape)
    else:
        mask = _splash.FullMask(shape)
    sizes = _splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(
            _splash.MultiHeadMask((mask,) * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, *, causal: bool, window: int | None,
                     scale: float):
    """Grouped-query self-attention through JAX's splash attention kernel,
    with its own backward pass: the S x S scores stay in VMEM.

    Takes and returns ``layers.sdpa``'s layout, (B, S, H, hd) queries and
    (B, S, K, hd) keys and values, H a multiple of K, S tiled by
    ``splash_block``. The kernel does not scale the queries: they are
    scaled here in float32 and rounded once to their dtype. Its softmax
    runs in float32."""
    _, S, H, _ = q.shape
    kernel = _splash_kernel(S, H, causal, window, splash_block(S),
                            interpret_mode())
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads_first = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    out = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
