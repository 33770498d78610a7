"""Jitted public wrappers for the Pallas kernels.

On the TPU they run compiled. On the CPU backend, where the test suite
runs, they run in Pallas interpret mode, validated against ``ref.py``
(megablox's grouped product against ``jax.lax.ragged_dot``); any other
backend is an error. The wrappers pad ragged shapes up to
block multiples and handle layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
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


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=128, block_k=128):
    S = q.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)
    pad = (-S) % bq
    if pad:
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(t, cfg) for t in (q, k, v))
    out = _fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk,
                              interpret=interpret_mode())
    return out[:, :S] if pad else out


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
