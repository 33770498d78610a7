"""Plain reference of the blocked GEMM job: C = A @ B, block by block.

The job's inputs are defined by the job, not by the engine: block (i, k)
of A is ``normal(fold_in(PRNGKey(seed_a), i * 65536 + k), (bs, bs)) /
sqrt(bs)`` in float32, and B likewise from ``seed_b``. This module makes
them anew with ``jax.random`` (the same threefry bits on any backend) and
forms each output block as a plain sum of products at ``HIGHEST``
precision, so its error against float64 is about 1e-6, far below what is
compared.

``mode="fp8"`` is the control: every operand rounded to float8 e4m3 with
one scale per block (its largest magnitude at e4m3's largest finite
value), then multiplied at ``HIGHEST``. It is the step below the
configuration's precision (one bfloat16 pass of the MXU) that would tempt
a later change, and it must fail the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnums=(3,))
def input_block(seed, i, k, bs: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i * 65536 + k)
    return jax.random.normal(key, (bs, bs), dtype=jnp.float32) / np.sqrt(bs)


def round_fp8(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 with one scale for the whole array."""
    big = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x)) / big
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def output_block(seed_a, seed_b, i, j, bs: int, nb: int, mode: str = "f32"
                 ) -> jax.Array:
    """Block (i, j) of C for an ``nb`` x ``nb`` grid of ``bs`` blocks."""
    acc = jnp.zeros((bs, bs), jnp.float32)
    for k in range(nb):
        a = input_block(seed_a, i, k, bs)
        b = input_block(seed_b, k, j, bs)
        if mode == "fp8":
            a, b = round_fp8(a), round_fp8(b)
        acc = acc + jnp.dot(a, b, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    return acc


def rel_fro_err(got: jax.Array, ref: jax.Array) -> float:
    """||got - ref||_F / ||ref||_F, summed in float32 on the device."""
    d = jnp.linalg.norm((got - ref).astype(jnp.float32))
    return float(d / jnp.linalg.norm(ref))
