"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + properties.

The suite runs on the CPU, so every raw kernel call asks for Pallas
interpret mode explicitly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip without the dev extra
    from _hypothesis_fallback import given, settings, st

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_attention import mlstm_chunk
from repro.kernels.ops import interpret_mode, splash_attention
from repro.kernels.ref import (
    decode_attention_ref,
    flash_attention_ref,
    mlstm_chunk_ref,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,bq,bk", [
    (1, 128, 2, 2, 64, 64, 64),      # MHA
    (2, 256, 4, 2, 64, 128, 64),     # GQA 2:1
    (1, 256, 8, 1, 32, 64, 128),     # MQA
    (2, 512, 4, 4, 128, 128, 128),   # bigger head_dim
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (False, None), (True, 128),
])
def test_flash_attention_sweep(dtype, B, S, H, K, hd, bq, bk, causal,
                               window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, S, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,bk", [
    (2, 512, 8, 2, 64, 128),
    (3, 1024, 4, 4, 32, 256),
    (1, 256, 16, 2, 128, 64),
])
def test_decode_attention_sweep(dtype, B, S, H, K, hd, bk):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (B, H, hd), dtype)
    kc = rand(ks[1], (B, S, K, hd), dtype)
    vc = rand(ks[2], (B, S, K, hd), dtype)
    kv_len = jnp.asarray([S, max(1, S // 2), 7][:B], dtype=jnp.int32)
    out = decode_attention(q, kc, vc, kv_len, block_k=bk, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 128, 2, 32, 32),
    (1, 256, 4, 64, 64),
    (2, 256, 1, 16, 128),
])
def test_mlstm_chunk_sweep(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = rand(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k = rand(ks[1], (B, S, H, hd), jnp.float32) * 0.5
    v = rand(ks[2], (B, S, H, hd), jnp.float32)
    log_f = jax.nn.log_sigmoid(rand(ks[3], (B, S, H), jnp.float32))
    i_g = jax.nn.sigmoid(rand(ks[4], (B, S, H), jnp.float32))
    out = mlstm_chunk(q, k, v, log_f, i_g, chunk=chunk, interpret=True)
    ref = mlstm_chunk_ref(q, k, v, log_f, i_g, chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-4)


@settings(max_examples=10, deadline=None)
@given(
    s_blocks=st.integers(1, 4),
    h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    causal=st.booleans(),
)
def test_flash_attention_property(s_blocks, h, g, causal):
    """Property: kernel == oracle for arbitrary block-aligned shapes and
    GQA group sizes."""
    S = 64 * s_blocks
    H, K, hd = h * g, h, 32
    ks = jax.random.split(jax.random.PRNGKey(S + H + causal), 3)
    q = rand(ks[0], (1, S, H, hd), jnp.float32)
    k = rand(ks[1], (1, S, K, hd), jnp.float32)
    v = rand(ks[2], (1, S, K, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_sdpa():
    """The kernel agrees with the model's attention oracle (layers.sdpa)."""
    from repro.models.layers import sdpa
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = rand(ks[0], (2, 128, 4, 64), jnp.float32)
    k = rand(ks[1], (2, 128, 2, 64), jnp.float32)
    v = rand(ks[2], (2, 128, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("B,S,H,K,hd,causal,window", [
    (2, 256, 2, 2, 64, True, None),      # one query head a KV head
    (2, 384, 6, 2, 64, True, None),      # three, in three blocks of 128
    (2, 256, 3, 1, 128, False, None),    # not causal, hd 128
    (1, 384, 3, 1, 64, True, 200),       # a window across blocks
    (1, 384, 2, 2, 64, False, 200),
])
def test_splash_attention_matches_sdpa(dtype, tol, B, S, H, K, hd, causal,
                                       window):
    """The fused kernel (interpreted) against ``layers.sdpa`` in float32
    on the same inputs: the output and the gradients of q, k and v, to
    float32 rounding, or bfloat16's for bfloat16 inputs."""
    from repro.models.layers import sdpa
    ks = jax.random.split(jax.random.PRNGKey(S + H + hd), 4)
    q = rand(ks[0], (B, S, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    d_out = rand(ks[3], (B, S, H, hd), dtype)
    out, vjp = jax.vjp(lambda *a: splash_attention(
        *a, causal=causal, window=window, scale=hd ** -0.5), q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: sdpa(
        *(t.astype(jnp.float32) for t in a), causal=causal, window=window),
        q, k, v)
    assert out.dtype == dtype and out.shape == ref.shape
    assert _rel(out, ref) < tol
    for got, want in zip(vjp(d_out), ref_vjp(d_out.astype(jnp.float32))):
        assert got.dtype == dtype
        assert _rel(got, want) < tol


def test_mlstm_kernel_matches_model_layer():
    """Kernel output matches repro.models.ssm.mlstm's inner computation
    (same gating math, zero initial state)."""
    from repro.configs import get_config, reduced
    from repro.models import ssm

    cfg = reduced(get_config("xlstm_350m"))
    p, _ = ssm.init_mlstm(jax.random.PRNGKey(3), cfg)
    x = rand(jax.random.PRNGKey(4), (2, 64, cfg.d_model), jnp.float32)
    y_layer, _ = ssm.mlstm(p, x, cfg)

    dk = int(cfg.mlstm_proj_factor * cfg.d_model)
    H, hd = cfg.n_heads, dk // cfg.n_heads
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, H, hd).astype(jnp.float32) * hd ** -0.5
    k = (x @ p["wk"]).reshape(B, S, H, hd).astype(jnp.float32)
    v = (x @ p["wv"]).reshape(B, S, H, hd).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(xf @ p["wf"])
    i_g = jnp.exp(jax.nn.log_sigmoid(xf @ p["wi"]))
    y_kernel = mlstm_chunk(q, k, v, log_f, i_g, chunk=64, interpret=True)
    y_kernel = y_kernel.reshape(B, S, dk) @ p["wo"]
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_layer),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("backend,expected", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError),
])
def test_interpret_mode_follows_backend(monkeypatch, backend, expected):
    """Interpret mode only on the CPU, compiled on the TPU, and any other
    backend refused instead of silently interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is RuntimeError:
        with pytest.raises(RuntimeError, match=backend):
            interpret_mode()
    else:
        assert interpret_mode() is expected
