"""Unified LM: init / forward / loss / decode for every assigned family.

Layer stacking: parameters for each *pattern position* are stacked over
``n_repeats`` along a leading "layers" axis and the repeated super-block
runs under ``jax.lax.scan`` — one lowered copy of the block HLO regardless
of depth (126-layer llama3-405b lowers as fast as 2 layers), and remat
applies per scan step.

Decode carries an explicit cache pytree (KV pages for attention, conv/ssm
state for Mamba, matrix state for mLSTM, scalar state for sLSTM), scanned
with the same stacking.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import mla, ssm
from repro.models.config import ModelConfig
from repro.models.layers import (
    EMBED,
    HEADS,
    INNER,
    KV,
    LAYERS,
    STATE,
    VOCAB,
    Params,
    attention,
    attention_decode,
    dtype_of,
    init_attention,
    init_mlp,
    init_moe,
    init_rmsnorm,
    mlp,
    moe_dropless,
    moe_mlp,
    rmsnorm,
    sdpa,
)

MAX_ABS_POS = 32768  # learned-position table for enc-dec (whisper decoder)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_mixer(key, mixer: str, cfg: ModelConfig) -> tuple[Params, Params]:
    if mixer == "attn":
        return init_attention(key, cfg)
    if mixer == "mla":
        return mla.init_mla(key, cfg)
    if mixer == "mamba":
        return ssm.init_mamba(key, cfg)
    if mixer == "mlstm":
        return ssm.init_mlstm(key, cfg)
    if mixer == "slstm":
        return ssm.init_slstm(key, cfg)
    raise ValueError(mixer)


def _init_block(key, entry: str, cfg: ModelConfig,
                cross: bool) -> tuple[Params, Params]:
    mixer, mlp_kind = cfg.mixer_of(entry), cfg.mlp_of(entry)
    ks = jax.random.split(key, 4)
    p: Params = {}
    s: Params = {}
    p["norm1"], s["norm1"] = init_rmsnorm(cfg)
    p["mixer"], s["mixer"] = _init_mixer(ks[0], mixer, cfg)
    if mlp_kind == "dense":
        p["norm2"], s["norm2"] = init_rmsnorm(cfg)
        p["mlp"], s["mlp"] = init_mlp(ks[1], cfg)
    elif mlp_kind == "moe":
        p["norm2"], s["norm2"] = init_rmsnorm(cfg)
        p["mlp"], s["mlp"] = init_moe(ks[1], cfg)
    if cross:
        p["cross_norm"], s["cross_norm"] = init_rmsnorm(cfg)
        p["cross"], s["cross"] = init_attention(ks[2], cfg, cross=True)
    return p, s


def _stack(trees: list[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def _stack_specs(spec: Any) -> Any:
    """Prepend the layers axis to every leaf spec (leaf specs are tuples)."""
    return jax.tree.map(
        lambda s: (LAYERS,) + s,
        spec,
        is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(e, (str, type(None))) for e in s),
    )


def init_model(key, cfg: ModelConfig) -> tuple[Params, Params]:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, cfg.n_layers + cfg.n_enc_layers + 8)
    ki = iter(range(len(ks)))
    p: Params = {}
    s: Params = {}

    p["embed"] = (jax.random.normal(ks[next(ki)], (cfg.vocab, cfg.d_model))
                  * 0.02).astype(dt)
    s["embed"] = (VOCAB, EMBED)

    # decoder blocks, stacked per pattern position
    blocks_p, blocks_s = [], []
    for r in range(cfg.n_repeats):
        row_p = []
        for entry in cfg.block_pattern:
            bp, bs = _init_block(ks[next(ki)], entry, cfg, cross=cfg.enc_dec)
            row_p.append(bp)
            if r == 0:
                blocks_s.append(_stack_specs(bs))
        blocks_p.append(row_p)
    p["blocks"] = [
        _stack([blocks_p[r][pos] for r in range(cfg.n_repeats)])
        for pos in range(cfg.pattern_period)
    ]
    s["blocks"] = blocks_s
    if cfg.n_dense_lead:
        lead = [_init_block(ks[next(ki)], cfg.lead_entry, cfg, cross=False)
                for _ in range(cfg.n_dense_lead)]
        p["lead"] = _stack([bp for bp, _ in lead])
        s["lead"] = _stack_specs(lead[0][1])

    if cfg.enc_dec:
        enc_p = []
        for r in range(cfg.n_enc_layers):
            bp, bs = _init_block(ks[next(ki)], "attn+dense", cfg, cross=False)
            enc_p.append(bp)
            if r == 0:
                s["enc_blocks"] = _stack_specs(bs)
        p["enc_blocks"] = _stack(enc_p)
        p["enc_norm"], s["enc_norm"] = init_rmsnorm(cfg)
        p["dec_pos"] = (jax.random.normal(
            ks[next(ki)], (MAX_ABS_POS, cfg.d_model)) * 0.02).astype(dt)
        s["dec_pos"] = (None, EMBED)

    p["final_norm"], s["final_norm"] = init_rmsnorm(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(
            ks[next(ki)], (cfg.d_model, cfg.vocab)) * 0.02).astype(dt)
        s["lm_head"] = (EMBED, VOCAB)
    return p, s


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _block_fwd(bp: Params, x: jax.Array, entry: str, cfg: ModelConfig,
               enc_out: jax.Array | None = None
               ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One layer; returns its output and its counters (dropless MoE only)."""
    mixer, mlp_kind = cfg.mixer_of(entry), cfg.mlp_of(entry)
    stats: dict[str, jax.Array] = {}
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        use_rope = not cfg.enc_dec
        y = attention(bp["mixer"], h, cfg, causal=True, use_rope=use_rope)
    elif mixer == "mla":
        y = mla.mla(bp["mixer"], h, cfg)
    elif mixer == "mamba":
        y, _ = ssm.mamba(bp["mixer"], h, cfg)
    elif mixer == "mlstm":
        y, _ = ssm.mlstm(bp["mixer"], h, cfg)
    elif mixer == "slstm":
        y, _ = ssm.slstm(bp["mixer"], h, cfg)
    else:
        raise ValueError(mixer)
    x = x + y
    if enc_out is not None:
        h = rmsnorm(bp["cross_norm"], x, cfg.norm_eps)
        x = x + attention(bp["cross"], h, cfg, causal=False, xkv=enc_out,
                          use_rope=False)
    if mlp_kind is not None:
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if mlp_kind != "moe":
            y = mlp(bp["mlp"], h, cfg)
        elif cfg.moe.dispatch == "dropless":
            y, stats = moe_dropless(bp["mlp"], h, cfg)
        else:
            y = moe_mlp(bp["mlp"], h, cfg)
        x = x + y
    return x, stats


def merge_stats(stats: list[dict[str, jax.Array]]) -> dict[str, jax.Array]:
    """Counters of several layers (each may be stacked) as one set: the
    balance losses, rows and drops summed, the load's maximum kept."""
    out: dict[str, jax.Array] = {}
    for st in stats:
        for name, v in st.items():
            v = jnp.max(v) if name == "moe_load_max" else jnp.sum(v)
            if name in out:
                v = jnp.maximum(out[name], v) if name == "moe_load_max" else out[name] + v
            out[name] = v
    return out


def _enc_block_fwd(bp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    x = x + attention(bp["mixer"], h, cfg, causal=False, use_rope=False)
    h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
    return x + mlp(bp["mlp"], h, cfg)


def _scan_blocks(params_stacked: Any, x: jax.Array, fwd) -> jax.Array:
    """scan a stacked block; ``fwd(block_params, x) -> x``."""

    def step(carry, bp):
        out = fwd(bp, carry)
        return out, None

    x, _ = jax.lax.scan(step, x, params_stacked)
    return x


def _scan_superblocks(p: Params, cfg: ModelConfig, x: jax.Array,
                      enc_out: jax.Array | None
                      ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """scan over n_repeats; each step applies the whole block pattern in
    order (preserves e.g. Jamba's 1:7 mamba:attn interleave). The leading
    dense layers, if any, run first under a scan of their own. Returns the
    output and the layers' counters, merged."""

    def run(blocks, pattern, x):
        def superblock(carry, bps):
            h = carry
            stats = []
            for pos, entry in enumerate(pattern):
                h, st = _block_fwd(bps[pos], h, entry, cfg, enc_out)
                stats.append(st)
            return h, stats

        f = jax.checkpoint(superblock) if cfg.remat else superblock
        if cfg.scan_layers:
            return jax.lax.scan(f, x, blocks)
        # unrolled: exact HLO-level cost analysis (dry-run roofline)
        ys = []
        for r in range(jax.tree.leaves(blocks)[0].shape[0]):
            x, st = f(x, jax.tree.map(lambda t: t[r], blocks))
            ys.append(st)
        return x, ys

    stats = []
    if cfg.n_dense_lead:
        x, st = run((p["lead"],), (cfg.lead_entry,), x)
        stats.append(st)
    x, st = run(tuple(p["blocks"]), cfg.block_pattern, x)
    stats.append(st)
    return x, merge_stats(jax.tree.leaves(
        stats, is_leaf=lambda t: isinstance(t, dict)))


def encode(p: Params, cfg: ModelConfig, enc_embeds: jax.Array) -> jax.Array:
    """Whisper encoder over precomputed (stub) frame embeddings."""
    fwd = _enc_block_fwd
    if cfg.remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    if cfg.scan_layers:
        x = _scan_blocks(p["enc_blocks"], enc_embeds,
                         lambda bp, h: fwd(bp, h, cfg))
    else:
        x = enc_embeds
        for r in range(cfg.n_enc_layers):
            bp = jax.tree.map(lambda t: t[r], p["enc_blocks"])
            x = fwd(bp, x, cfg)
    return rmsnorm(p["enc_norm"], x, cfg.norm_eps)


def forward_with_stats(
    p: Params,
    cfg: ModelConfig,
    tokens: jax.Array,                    # (B, S) int32
    enc_embeds: jax.Array | None = None,  # (B, F, d) stub frontend
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Token logits (B, S, vocab) for training / prefill, and the layers'
    counters (``merge_stats``; empty but for dropless MoE layers)."""
    x = p["embed"][tokens].astype(dtype_of(cfg))
    enc_out = None
    if cfg.enc_dec:
        assert enc_embeds is not None
        enc_out = encode(p, cfg, enc_embeds.astype(dtype_of(cfg)))
        S = tokens.shape[1]
        x = x + p["dec_pos"][:S][None]

    x, stats = _scan_superblocks(p, cfg, x, enc_out)

    x = rmsnorm(p["final_norm"], x, cfg.norm_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return (x @ head).astype(jnp.float32), stats


def forward(p: Params, cfg: ModelConfig, tokens: jax.Array,
            enc_embeds: jax.Array | None = None) -> jax.Array:
    """Token logits for training / prefill. Returns (B, S, vocab)."""
    return forward_with_stats(p, cfg, tokens, enc_embeds)[0]


def loss_fn(
    p: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    labels: jax.Array,
    enc_embeds: jax.Array | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mean cross-entropy plus the z-loss, plus the MoE layers' balance
    losses where they have one; and the layers' counters."""
    logits, stats = forward_with_stats(p, cfg, tokens, enc_embeds)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    ce = (logz - gold).mean()
    zloss = 1e-4 * jnp.square(logz).mean()   # logit drift regularizer
    loss = ce + zloss
    if "moe_aux" in stats:
        loss = loss + stats["moe_aux"]
    return loss, stats


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def _attn_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def _refuse_latent_decode(cfg: ModelConfig) -> None:
    if cfg.mla is not None or cfg.n_dense_lead:
        raise NotImplementedError(
            f"{cfg.name}: decoding through a latent (MLA) cache, and leading "
            "dense layers in decode, are not implemented; training and "
            "prefill (forward) are")


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               abstract: bool = False) -> Any:
    """Decode-state pytree. One entry per pattern position, leaves stacked
    over n_repeats. ``abstract=True`` returns ShapeDtypeStructs (dry-run)."""
    _refuse_latent_decode(cfg)
    R = cfg.n_repeats
    K, hd = cfg.n_kv_heads, cfg.hd
    dt = dtype_of(cfg)

    def mk(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype=dtype)

    cache: list[dict[str, Any]] = []
    for entry in cfg.block_pattern:
        mixer = cfg.mixer_of(entry)
        c: dict[str, Any] = {}
        if mixer == "attn":
            S = _attn_cache_len(cfg, seq_len)
            c["k"] = mk((R, batch, S, K, hd), dt)
            c["v"] = mk((R, batch, S, K, hd), dt)
        elif mixer == "mamba":
            c["conv"] = mk((R, batch, cfg.ssm_conv_width - 1, cfg.d_inner), dt)
            c["ssm"] = mk((R, batch, cfg.d_inner, cfg.ssm_state_dim),
                          jnp.float32)
        elif mixer == "mlstm":
            dk = int(cfg.mlstm_proj_factor * cfg.d_model)
            hdm = dk // cfg.n_heads
            c["C"] = mk((R, batch, cfg.n_heads, hdm, hdm), jnp.float32)
            c["n"] = mk((R, batch, cfg.n_heads, hdm), jnp.float32)
        elif mixer == "slstm":
            c["c"] = mk((R, batch, cfg.d_model), jnp.float32)
            c["h"] = mk((R, batch, cfg.d_model), jnp.float32)
        if cfg.enc_dec:
            c["cross_k"] = mk((R, batch, cfg.enc_frames, K, hd), dt)
            c["cross_v"] = mk((R, batch, cfg.enc_frames, K, hd), dt)
        cache.append(c)
    return cache


def cache_specs(cfg: ModelConfig) -> list[dict[str, Any]]:
    """Logical-axis specs paralleling init_cache output."""
    specs: list[dict[str, Any]] = []
    for entry in cfg.block_pattern:
        mixer = cfg.mixer_of(entry)
        c: dict[str, Any] = {}
        if mixer == "attn":
            c["k"] = (LAYERS, "batch", "kv_seq", KV, None)
            c["v"] = (LAYERS, "batch", "kv_seq", KV, None)
        elif mixer == "mamba":
            c["conv"] = (LAYERS, "batch", None, INNER)
            c["ssm"] = (LAYERS, "batch", INNER, STATE)
        elif mixer == "mlstm":
            c["C"] = (LAYERS, "batch", HEADS, None, None)
            c["n"] = (LAYERS, "batch", HEADS, None)
        elif mixer == "slstm":
            c["c"] = (LAYERS, "batch", EMBED)
            c["h"] = (LAYERS, "batch", EMBED)
        if cfg.enc_dec:
            c["cross_k"] = (LAYERS, "batch", None, KV, None)
            c["cross_v"] = (LAYERS, "batch", None, KV, None)
        specs.append(c)
    return specs


def _block_decode(bp: Params, c: dict[str, Any], x: jax.Array,
                  pos: jax.Array, entry: str, cfg: ModelConfig
                  ) -> tuple[jax.Array, dict[str, Any]]:
    mixer, mlp_kind = cfg.mixer_of(entry), cfg.mlp_of(entry)
    newc = dict(c)
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        rotating = cfg.sliding_window is not None and \
            c["k"].shape[1] <= cfg.sliding_window
        y, k, v = attention_decode(
            bp["mixer"], h, c["k"], c["v"], pos, cfg,
            use_rope=not cfg.enc_dec, rotating=rotating)
        newc["k"], newc["v"] = k, v
    elif mixer == "mamba":
        y, (conv, st) = ssm.mamba(bp["mixer"], h, cfg,
                                  state=(c["conv"], c["ssm"]))
        newc["conv"], newc["ssm"] = conv, st
    elif mixer == "mlstm":
        y, (C, n) = ssm.mlstm_decode_step(bp["mixer"], h, cfg,
                                          (c["C"], c["n"]))
        newc["C"], newc["n"] = C, n
    elif mixer == "slstm":
        y, (cc, hh) = ssm.slstm(bp["mixer"], h, cfg, state=(c["c"], c["h"]))
        newc["c"], newc["h"] = cc, hh
    else:
        raise ValueError(mixer)
    x = x + y
    if cfg.enc_dec:
        h = rmsnorm(bp["cross_norm"], x, cfg.norm_eps)
        y = sdpa((h @ bp["cross"]["wq"]).reshape(
            x.shape[0], 1, cfg.n_heads, cfg.hd),
            c["cross_k"], c["cross_v"], causal=False)
        x = x + y.reshape(x.shape[0], 1, -1) @ bp["cross"]["wo"]
    if mlp_kind is not None:
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        y = (moe_mlp(bp["mlp"], h, cfg) if mlp_kind == "moe"
             else mlp(bp["mlp"], h, cfg))
        x = x + y
    return x, newc


def decode_step(
    p: Params,
    cfg: ModelConfig,
    cache: Any,
    token: jax.Array,          # (B,) int32 — the newest token
    pos: jax.Array,            # scalar int32 — its position
) -> tuple[jax.Array, Any]:
    """One serving step: append token at ``pos``, return next-token logits
    (B, vocab) and the updated cache."""
    _refuse_latent_decode(cfg)
    x = p["embed"][token][:, None, :].astype(dtype_of(cfg))  # (B,1,d)
    if cfg.enc_dec:
        x = x + p["dec_pos"][pos][None, None, :]

    def superblock(carry, inp):
        h = carry
        bps, cs = inp
        newcs = []
        for posn, entry in enumerate(cfg.block_pattern):
            h, nc = _block_decode(bps[posn], cs[posn], h, pos, entry, cfg)
            newcs.append(nc)
        return h, tuple(newcs)

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(
            superblock, x, (tuple(p["blocks"]), tuple(cache)))
        new_cache = list(new_cache)
    else:
        ys = []
        for r in range(cfg.n_repeats):
            inp = jax.tree.map(lambda t: t[r],
                               (tuple(p["blocks"]), tuple(cache)))
            x, nc = superblock(x, inp)
            ys.append(nc)
        new_cache = list(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *ys))

    x = rmsnorm(p["final_norm"], x, cfg.norm_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = (x[:, 0, :] @ head).astype(jnp.float32)
    return logits, new_cache


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct tree of the parameters (no allocation; dry-run)."""
    return jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)[0])


def model_specs(cfg: ModelConfig) -> Params:
    """Logical-axis spec tree paralleling ``abstract_params`` — built under
    ``eval_shape`` so no parameter memory is ever allocated."""
    cell: dict[str, Any] = {}

    def build():
        p, s = init_model(jax.random.PRNGKey(0), cfg)
        cell["specs"] = s
        return p

    jax.eval_shape(build)
    return cell["specs"]
