"""Support Vector Classification (paper §V, Fig. 11).

The paper runs SVC from the Dask-ML benchmark suite with growing sample
counts. We implement a linear SVM trained by full-batch sub-gradient
descent on the hinge loss, blocked over sample chunks: each iteration is a
wide fan-out (per-block gradients), a fan-in reduction tree, and an update
task that feeds the next iteration — a DAG with the bursty fan-out/fan-in
cadence that characterizes data-parallel ML, unrolled for ``n_iters``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import GraphBuilder
from repro.core.dag import DAG

DIM = 32


@functools.partial(jax.jit, static_argnums=(2,))
def _data_block(seed, i, rows: int) -> tuple[jax.Array, jax.Array]:
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (rows, DIM), dtype=jnp.float32)
    w_true = jax.random.normal(jax.random.PRNGKey(seed + 999), (DIM,),
                               dtype=jnp.float32)
    y = jnp.sign(x @ w_true + 0.1)
    return x, y


@jax.jit
def _hinge_grad(block: tuple[jax.Array, jax.Array],
                w: jax.Array) -> jax.Array:
    x, y = block
    margin = y * (x @ w)
    active = (margin < 1.0).astype(jnp.float32)
    return -(x * (y * active)[:, None]).sum(axis=0)


@jax.jit
def _apply_update(w: jax.Array, grad_sum: jax.Array, n: float,
                  lr: float, reg: float) -> jax.Array:
    return (1.0 - lr * reg) * w - lr * grad_sum / n


def svc_dag(
    n_samples: int,
    n_blocks: int = 8,
    n_iters: int = 4,
    lr: float = 0.1,
    reg: float = 1e-3,
    seed: int = 5,
    ms_per_flop: float = 0.0,
) -> DAG:
    if n_samples % n_blocks:
        raise ValueError("n_samples must divide into n_blocks")
    rows = n_samples // n_blocks
    grad_flops = 4.0 * rows * DIM

    def costed(fn):
        from repro.apps.costing import flop_costed

        return flop_costed(fn, grad_flops, ms_per_flop)

    g = GraphBuilder()

    def leaf(i: int):
        def make():
            return _data_block(seed, i, rows)

        make.__name__ = "svc_block"
        return make

    blocks = [g.add(leaf(i), name=f"svc-X-{i}") for i in range(n_blocks)]

    def init_w():
        return jnp.zeros((DIM,), dtype=jnp.float32)

    init_w.__name__ = "svc_init"
    w = g.add(init_w, name="svc-w0")

    for it in range(n_iters):
        grads = [g.add(costed(_hinge_grad), blk, w,
                       name=f"svc-g{it}-{i}")
                 for i, blk in enumerate(blocks)]
        depth = 0
        while len(grads) > 1:
            nxt = []
            for i in range(0, len(grads) - 1, 2):
                nxt.append(g.add(jnp.add, grads[i], grads[i + 1],
                                 name=f"svc-gs{it}-{depth}-{i // 2}"))
            if len(grads) % 2:
                nxt.append(grads[-1])
            grads, depth = nxt, depth + 1
        w = g.add(
            functools.partial(_apply_update, n=float(n_samples), lr=lr,
                              reg=reg),
            w, grads[0], name=f"svc-w{it + 1}",
        )
    return g.build()


def svc_expected(n_samples: int, n_blocks: int = 8, n_iters: int = 4,
                 lr: float = 0.1, reg: float = 1e-3,
                 seed: int = 5) -> np.ndarray:
    rows = n_samples // n_blocks
    w = jnp.zeros((DIM,), dtype=jnp.float32)
    blocks = [_data_block(seed, i, rows) for i in range(n_blocks)]
    for _ in range(n_iters):
        gsum = None
        for blk in blocks:
            gb = _hinge_grad(blk, w)
            gsum = gb if gsum is None else gsum + gb
        w = _apply_update(w, gsum, float(n_samples), lr, reg)
    return np.asarray(w)
