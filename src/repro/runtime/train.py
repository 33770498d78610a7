"""Training step: loss + grads + AdamW, with microbatch accumulation.

``build_train_step`` returns a pure function
``(params, opt_state, batch) -> (params, opt_state, metrics)`` ready for
``jax.jit`` with shardings. ``metrics`` carries the loss (with any MoE
balance loss in it), the gradient norm, the schedule's scale and the
model's counters (``models.model.merge_stats``) as device scalars.
Gradient accumulation over microbatches is a ``lax.scan`` so activation
memory is one microbatch while the weight gradient buffer lives across the
scan (standard large-batch trick; also the knob §Perf turns for
memory-bound cells).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_update
from repro.optim.schedules import cosine_schedule


def build_train_step(cfg: ModelConfig, opt: AdamWConfig,
                     n_microbatches: int = 1):
    def loss_of(params, tokens, labels, enc):
        return M.loss_fn(params, cfg, tokens, labels, enc)

    def train_step(params, opt_state, batch):
        tokens = batch["tokens"]
        labels = batch["labels"]
        enc = batch.get("enc_embeds")
        if n_microbatches == 1:
            (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, tokens, labels, enc)
        else:
            B = tokens.shape[0]
            assert B % n_microbatches == 0
            mb = B // n_microbatches

            def split(x):
                return x.reshape((n_microbatches, mb) + x.shape[1:])

            mtok, mlab = split(tokens), split(labels)
            menc = split(enc) if enc is not None else None

            def acc_step(carry, xs):
                loss_acc, g_acc = carry
                t, l = xs[0], xs[1]
                e = xs[2] if menc is not None else None
                (loss, st), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, t, l, e)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (loss_acc + loss, g_acc), st

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            xs = (mtok, mlab) + ((menc,) if menc is not None else ())
            (loss, grads), stats = jax.lax.scan(
                acc_step, (jnp.zeros(()), zeros), xs)
            loss = loss / n_microbatches
            grads = jax.tree.map(lambda g: g / n_microbatches, grads)
            stats = M.merge_stats([stats])
            if "moe_aux" in stats:      # a part of the loss: its mean
                stats["moe_aux"] = stats["moe_aux"] / n_microbatches

        lr_scale = cosine_schedule(opt_state["count"], warmup=opt.warmup)
        params, opt_state, om = adamw_update(
            grads, opt_state, params, opt, lr_scale)
        metrics = {"loss": loss, "grad_norm": om["grad_norm"],
                   "lr_scale": lr_scale, **stats}
        return params, opt_state, metrics

    return train_step


def synthetic_batch(cfg: ModelConfig, batch: int, seq: int, seed: int = 0,
                    abstract: bool = False) -> dict[str, Any]:
    """Synthetic token batch (data pipeline stand-in / dry-run specs)."""
    if abstract:
        out = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        }
        if cfg.enc_dec:
            out["enc_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.enc_frames, cfg.d_model), jnp.bfloat16
                if cfg.dtype == "bfloat16" else jnp.float32)
        return out
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
    out = {"tokens": toks,
           "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.enc_dec:
        out["enc_embeds"] = jax.random.normal(
            key, (batch, cfg.enc_frames, cfg.d_model),
            dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    return out
