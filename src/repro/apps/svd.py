"""SVD workloads (paper §V, Figs. 9 & 10).

SVD1 — tall-and-skinny SVD via the communication-avoiding TSQR algorithm
(the same algorithm Dask uses for ``da.linalg.svd`` on tall matrices):
block the rows, QR each block, reduce the R factors pairwise with stacked
QRs, SVD the final small R, then fan the right factor back out to form U.
The DAG is a reduction tree followed by a wide fan-out: exactly the shape
WUKONG's fan-in counters + proxy are built for.

SVD2 — rank-k randomized SVD of a square n x n matrix (Halko, Martinsson,
Tropp — the paper's citation [18]): Y = A @ Omega, QR(Y), B = Q^T A,
SVD(B). Blocked over row-blocks of A.

``ideal_storage=True`` reproduces the paper's §V-C "ideally-fast
intermediate storage" ablation: every input block is regenerated from its
seed instead of being read back from the KV store, which removes the
large-object KV traffic while keeping the DAG and compute identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import GraphBuilder
from repro.core.dag import DAG


@functools.partial(jax.jit, static_argnums=(2, 3))
def _row_block(seed, i, rows: int, cols: int) -> jax.Array:
    # i is traced: one executable for all row blocks of a given shape
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    return jax.random.normal(key, (rows, cols), dtype=jnp.float32)


@jax.jit
def _qr_r(a: jax.Array) -> jax.Array:
    return jnp.linalg.qr(a, mode="r")


@jax.jit
def _stack_qr_r(r1: jax.Array, r2: jax.Array) -> jax.Array:
    return jnp.linalg.qr(jnp.concatenate([r1, r2], axis=0), mode="r")


@jax.jit
def _singular_values(r: jax.Array) -> jax.Array:
    return jnp.linalg.svd(r, compute_uv=False)


def _costed(fn, flops, ms_per_flop=0.0):
    """Per-task compute cost from analytic FLOPs (see
    repro.apps.costing.flop_costed)."""
    from repro.apps.costing import flop_costed

    return flop_costed(fn, flops, ms_per_flop)


def tsqr_svd_dag(
    rows: int,
    cols: int = 64,
    n_blocks: int = 8,
    seed: int = 3,
    compute_u: bool = True,
    ms_per_flop: float = 0.0,
) -> DAG:
    """SVD1: tall-and-skinny (rows >> cols) SVD via TSQR.

    ``ms_per_flop`` (simulated, clock-charged) simulates compute duration
    per task from analytic FLOPs (single-core container; same methodology
    as TR's delays)."""
    if rows % n_blocks:
        raise ValueError("rows must divide evenly into n_blocks")
    block_rows = rows // n_blocks
    qr_flops = 2.0 * block_rows * cols ** 2
    g = GraphBuilder()

    def leaf(i: int):
        def make() -> jax.Array:
            return _row_block(seed, i, block_rows, cols)

        make.__name__ = "svd_block"
        return make

    blocks = [g.add(leaf(i), name=f"svd1-A-{i}") for i in range(n_blocks)]
    rs = [g.add(_costed(_qr_r, qr_flops, ms_per_flop), blk,
                name=f"svd1-R0-{i}")
          for i, blk in enumerate(blocks)]
    depth = 0
    while len(rs) > 1:
        nxt = []
        for i in range(0, len(rs) - 1, 2):
            nxt.append(g.add(_stack_qr_r, rs[i], rs[i + 1],
                             name=f"svd1-R{depth + 1}-{i // 2}"))
        if len(rs) % 2:
            nxt.append(rs[-1])
        rs, depth = nxt, depth + 1
    final_r = rs[0]
    g.add(_singular_values, final_r, name="svd1-S")

    if compute_u:
        # Fan-out: U_i = A_i @ V @ diag(1/s) — wide fan-out from final R.
        @jax.jit
        def u_block(a_blk: jax.Array, r: jax.Array) -> jax.Array:
            u, s, vt = jnp.linalg.svd(r, full_matrices=False)
            return a_blk @ vt.T / s[None, :]

        for i, blk in enumerate(blocks):
            g.add(_costed(u_block, 2.0 * block_rows * cols ** 2,
                          ms_per_flop),
                  blk, final_r, name=f"svd1-U-{i}")
    return g.build()


def tsqr_singular_values_expected(rows: int, cols: int, n_blocks: int,
                                  seed: int = 3) -> np.ndarray:
    block_rows = rows // n_blocks
    A = np.concatenate(
        [np.asarray(_row_block(seed, i, block_rows, cols))
         for i in range(n_blocks)], axis=0)
    return np.linalg.svd(A, compute_uv=False)


def randomized_svd_dag(
    n: int,
    rank: int = 5,
    oversample: int = 5,
    n_blocks: int = 8,
    seed: int = 4,
    ideal_storage: bool = False,
    ms_per_flop: float = 0.0,
) -> DAG:
    """SVD2: rank-``rank`` randomized SVD of an n x n matrix [Halko et al.].

    The square matrix is blocked by rows. ``ideal_storage`` regenerates
    A-blocks inside consumers instead of passing them through the KV store
    (paper §V-C's ideal-storage ablation — "all array data was randomly
    generated each time it was used").
    """
    if n % n_blocks:
        raise ValueError("n must divide evenly into n_blocks")
    rows = n // n_blocks
    k = rank + oversample
    blk_mm_flops = 2.0 * rows * n * k        # Y_i / B_i block products
    g = GraphBuilder()

    def costed(fn, flops=blk_mm_flops):
        return _costed(fn, flops, ms_per_flop)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def omega(seed2: int, nn: int) -> jax.Array:
        return jax.random.normal(
            jax.random.PRNGKey(seed2), (nn, k), dtype=jnp.float32)

    def make_omega() -> jax.Array:
        return omega(seed + 1, n)

    make_omega.__name__ = "svd2_omega"
    om = g.add(make_omega, name="svd2-Omega")

    def leaf(i: int):
        def make() -> jax.Array:
            return _row_block(seed, i, rows, n)

        make.__name__ = "svd2_block"
        return make

    if ideal_storage:
        # A-blocks are regenerated in place inside every consumer: zero
        # intermediate-storage traffic for the big objects.
        @jax.jit
        def y_block_ideal(i, om_: jax.Array) -> jax.Array:
            return _row_block(seed, i, rows, n) @ om_

        ys = [g.add(costed(functools.partial(y_block_ideal, jnp.int32(i))),
                    om, name=f"svd2-Y-{i}") for i in range(n_blocks)]
    else:
        blocks = [g.add(leaf(i), name=f"svd2-A-{i}") for i in range(n_blocks)]

        @jax.jit
        def y_block(a_blk: jax.Array, om_: jax.Array) -> jax.Array:
            return a_blk @ om_

        ys = [g.add(costed(y_block), blk, om, name=f"svd2-Y-{i}")
              for i, blk in enumerate(blocks)]

    # TSQR on Y (n x k, tall-skinny) to get Q implicitly via R, then
    # B^T = A^T Q computed blockwise; SVD of B gives the rank-k factors.
    rs = [g.add(_qr_r, y, name=f"svd2-R0-{i}") for i, y in enumerate(ys)]
    depth = 0
    while len(rs) > 1:
        nxt = []
        for i in range(0, len(rs) - 1, 2):
            nxt.append(g.add(_stack_qr_r, rs[i], rs[i + 1],
                             name=f"svd2-R{depth + 1}-{i // 2}"))
        if len(rs) % 2:
            nxt.append(rs[-1])
        rs, depth = nxt, depth + 1
    final_r = rs[0]

    @jax.jit
    def q_block(y: jax.Array, r: jax.Array) -> jax.Array:
        # Q_i = Y_i R^{-1}
        return jax.scipy.linalg.solve_triangular(r.T, y.T, lower=True).T

    qs = [g.add(costed(q_block, 2.0 * rows * k * k), y, final_r,
                name=f"svd2-Q-{i}")
          for i, y in enumerate(ys)]

    if ideal_storage:
        @jax.jit
        def bt_block_ideal(i, q: jax.Array) -> jax.Array:
            return _row_block(seed, i, rows, n).T @ q

        bts = [g.add(costed(functools.partial(bt_block_ideal, jnp.int32(i))),
                     q, name=f"svd2-Bt-{i}") for i, q in enumerate(qs)]
    else:
        @jax.jit
        def bt_block(a_blk: jax.Array, q: jax.Array) -> jax.Array:
            return a_blk.T @ q

        bts = [g.add(costed(bt_block), blk, q, name=f"svd2-Bt-{i}")
               for i, (blk, q) in enumerate(zip(blocks, qs))]

    @jax.jit
    def sum2(a: jax.Array, b: jax.Array) -> jax.Array:
        return a + b

    acc = bts
    depth = 0
    while len(acc) > 1:
        nxt = []
        for i in range(0, len(acc) - 1, 2):
            nxt.append(g.add(sum2, acc[i], acc[i + 1],
                             name=f"svd2-BtSum{depth}-{i // 2}"))
        if len(acc) % 2:
            nxt.append(acc[-1])
        acc, depth = nxt, depth + 1

    @functools.partial(jax.jit, static_argnums=(1,))
    def top_singular_values(bt: jax.Array, r: int) -> jax.Array:
        return jnp.linalg.svd(bt.T, compute_uv=False)[:r]

    g.add(functools.partial(top_singular_values, r=rank), acc[0],
          name="svd2-S")
    return g.build()


def randomized_svd_expected(n: int, rank: int, oversample: int,
                            n_blocks: int, seed: int = 4) -> np.ndarray:
    rows = n // n_blocks
    A = np.concatenate([np.asarray(_row_block(seed, i, rows, n))
                        for i in range(n_blocks)], axis=0)
    Om = np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed + 1), (n, rank + oversample),
        dtype=jnp.float32))
    Y = A @ Om
    Q, _ = np.linalg.qr(Y)
    B = Q.T @ A
    return np.linalg.svd(B, compute_uv=False)[:rank]
