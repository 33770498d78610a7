"""Which path ``layers.attention`` takes, and that the fused one computes
what ``sdpa`` computes.

The fused kernel runs only on the TPU. Here the backend check is patched
to the TPU's answer, so the kernel runs in Pallas interpret mode on the
CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import layers
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.train import build_train_step, synthetic_batch


@pytest.fixture
def tally():
    layers.ATTENTION_PATHS.clear()
    yield layers.ATTENTION_PATHS
    layers.ATTENTION_PATHS.clear()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)


def _llama():
    """A two-layer llama-family model: 3 query heads a KV head of 64."""
    return dataclasses.replace(reduced(get_config("smollm_360m")), n_layers=2,
                               n_heads=3, n_kv_heads=1, head_dim=64, remat=True)


def _loss_and_grads(cfg, batch):
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg)

    def loss(p):
        return M.loss_fn(p, cfg, batch["tokens"], batch["labels"])[0]
    return jax.jit(jax.value_and_grad(loss))(params)


def test_fused_attention_matches_sdpa_in_loss_and_grads(monkeypatch, tally):
    cfg = _llama()
    batch = synthetic_batch(cfg, 2, 128, seed=5)
    want_loss, want = _loss_and_grads(cfg, batch)
    assert tally == {"sdpa/backend": 1}
    tally.clear()
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    got_loss, got = _loss_and_grads(cfg, batch)
    assert tally == {"fused": 1}
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.linalg.norm(g - w) <= 1e-4 * np.linalg.norm(w)


def _trace_attention(cfg, S, cross=False):
    p, _ = layers.init_attention(jax.random.PRNGKey(0), cfg, cross=cross)
    x = jax.ShapeDtypeStruct((2, S, cfg.d_model), jnp.float32)
    xkv = jax.ShapeDtypeStruct((2, 8, cfg.d_model), jnp.float32) if cross else None
    jax.eval_shape(lambda x, xkv: layers.attention(p, x, cfg, xkv=xkv), x, xkv)


@pytest.mark.parametrize("arch,S,cross,path", [
    ("smollm_360m", 256, False, "fused"),          # tiled self-attention
    ("mixtral_8x7b", 128, False, "fused"),         # a sliding window of 32
    ("smollm_360m", 96, False, "sdpa/untiled"),    # no block divides 96
    ("whisper_large_v3", 128, True, "sdpa/cross"),
])
def test_tally_names_the_path_and_the_reason(on_tpu, tally, arch, S, cross, path):
    _trace_attention(reduced(get_config(arch)), S, cross)
    assert tally == {path: 1}


def test_cpu_keeps_sdpa(tally):
    _trace_attention(reduced(get_config("smollm_360m")), 256)
    assert tally == {"sdpa/backend": 1}


def _sdpa_attention(p, x, cfg, *, causal=True, positions=None, xkv=None,
                    use_rope=True):
    """Attention as it was before the fused path: always ``sdpa``."""
    B, S, _ = x.shape
    src = x if xkv is None else xkv
    q, k, v = layers._project_qkv(p, x, src, cfg)
    if use_rope and xkv is None:
        pos = positions if positions is not None else jnp.arange(S)
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
    out = layers.sdpa(q, k, v, causal=causal and xkv is None,
                      window=cfg.sliding_window if xkv is None else None)
    return out.reshape(B, S, -1) @ p["wo"]


def _lowered_smollm_step(B, S):
    cfg = get_config("smollm_360m")
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg)[0])
    opt = jax.eval_shape(adamw_init, params)
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    step = jax.jit(build_train_step(cfg, AdamWConfig()), donate_argnums=(0, 1))
    return step.lower(params, opt, batch)


def test_cpu_lowers_the_smollm_step_as_before(monkeypatch, tally):
    """On the CPU the full-size SmolLM step lowers to the very program
    that always-``sdpa`` attention gives, and its ops carry no new scope."""
    got = _lowered_smollm_step(4, 2048)
    assert tally == {"sdpa/backend": 1}
    assert "attention_fused" not in got.as_text(debug_info=True)
    monkeypatch.setattr(M, "attention", _sdpa_attention)
    assert got.as_text() == _lowered_smollm_step(4, 2048).as_text()
