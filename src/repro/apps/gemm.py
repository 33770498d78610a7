"""Blocked General Matrix Multiplication (paper §V, Fig. 8).

C = A @ B with A, B split into a bxb grid of square blocks. Leaf tasks
materialize input blocks (seeded PRNG — the paper's client also does not
ship the matrices through the scheduler), inner tasks compute block
products on the MXU-analog (jitted jnp.dot) and a reduction tree sums the
partial products per output block, giving the large fan-out/fan-in
structure that exercises WUKONG's proxy and dependency counters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import GraphBuilder
from repro.core.dag import DAG


@functools.partial(jax.jit, static_argnums=(3,))
def _block(seed, i, j, bs: int) -> jax.Array:
    # i, j are traced: ONE compiled executable serves every block
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i * 65536 + j)
    return jax.random.normal(key, (bs, bs), dtype=jnp.float32) / np.sqrt(bs)


@jax.jit
def _matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


@jax.jit
def _add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def gemm_dag(n: int, block_size: int, seed_a: int = 1, seed_b: int = 2,
             ms_per_flop: float = 0.0) -> DAG:
    """DAG computing C = A @ B for n x n matrices in block_size blocks.

    Roots are the bxb output blocks ``gemm-C-i-j``. ``ms_per_flop`` adds
    a simulated compute duration per task proportional to its analytic
    FLOPs, charged on the engine clock — the knob that emulates the
    paper's compute-heavy regime on a single-core container (same
    methodology as TR's delays, paper Fig. 4).
    """
    from repro.apps.costing import flop_costed

    def costed(fn, flops):
        return flop_costed(fn, flops, ms_per_flop)

    if n % block_size:
        raise ValueError("n must be divisible by block_size")
    b = n // block_size
    mm_flops = 2.0 * block_size ** 3
    add_flops = float(block_size ** 2)
    g = GraphBuilder()

    def leaf(seed: int, i: int, j: int, tag: str):
        def make() -> jax.Array:
            return _block(seed, i, j, block_size)

        make.__name__ = f"gemm_block_{tag}"
        return make

    A = {(i, k): g.add(leaf(seed_a, i, k, "A"), name=f"gemm-A-{i}-{k}")
         for i in range(b) for k in range(b)}
    B = {(k, j): g.add(leaf(seed_b, k, j, "B"), name=f"gemm-B-{k}-{j}")
         for k in range(b) for j in range(b)}

    for i in range(b):
        for j in range(b):
            partials = [
                g.add(costed(_matmul, mm_flops), A[(i, k)], B[(k, j)],
                      name=f"gemm-P-{i}-{j}-{k}")
                for k in range(b)
            ]
            # pairwise reduction tree over k
            depth = 0
            while len(partials) > 1:
                nxt = []
                for s in range(0, len(partials) - 1, 2):
                    nxt.append(
                        g.add(costed(_add, add_flops),
                              partials[s], partials[s + 1],
                              name=f"gemm-S-{i}-{j}-{depth}-{s // 2}")
                    )
                if len(partials) % 2:
                    nxt.append(partials[-1])
                partials, depth = nxt, depth + 1
            final = partials[0]
            # alias the root with a stable name
            g.add(lambda x: x, final, name=f"gemm-C-{i}-{j}")
    return g.build()


def gemm_expected(n: int, block_size: int, seed_a: int = 1,
                  seed_b: int = 2) -> np.ndarray:
    b = n // block_size
    A = np.block([[np.asarray(_block(seed_a, i, k, block_size))
                   for k in range(b)] for i in range(b)])
    B = np.block([[np.asarray(_block(seed_b, k, j, block_size))
                   for j in range(b)] for k in range(b)])
    return A @ B
