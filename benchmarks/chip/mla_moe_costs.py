"""The yardstick's arithmetic for latent attention with routed and shared experts.

From the configuration file's ``model`` block (Hugging Face keys of
DeepSeek-V2) alone, never from the program under test. ``n_routed_experts``
counts the experts held on the chip, ``router_experts`` those the router
scores.
"""
from __future__ import annotations


def mla_moe_params(c: dict) -> dict[str, float]:
    """Weights a token meets in a matrix product, by part, per model.

    ``routed`` counts the held experts at the share of a token that they
    take on average: ``num_experts_per_tok x n_routed_experts /
    router_experts`` experts a token. The embedding's lookup is no product;
    the output head is.
    """
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    lead = c["first_k_dense_replace"]
    moe_layers = c["num_hidden_layers"] - lead
    expert = 3 * d * c["moe_intermediate_size"]
    attn = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) + H * vd * d
    share = c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_experts"]
    parts = {
        "attention": float(c["num_hidden_layers"] * attn),
        "dense": float(lead * 3 * d * c["intermediate_size"]),
        "shared": float(moe_layers * c["n_shared_experts"] * expert),
        "router": float(moe_layers * d * c["router_experts"]),
        "routed": float(moe_layers * share * expert),
        "head": float(d * V),
    }
    parts["matmul"] = sum(parts.values())
    return parts


def mla_moe_train_flops(c: dict, batch: int, seq: int) -> float:
    """Model operations of one training step, forward and backward.

    6 N D for the weight products (N = ``matmul`` above, D = batch x seq
    tokens), plus 6 L H (qk + v) S D for the scores and the values over the
    whole S x S square, as ``costs.dense_lm_train_flops`` counts them.
    Recomputation is not counted.
    """
    tokens = batch * seq
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attn = 6.0 * c["num_hidden_layers"] * c["num_attention_heads"] * (qk + c["v_head_dim"]) * seq
    return 6.0 * mla_moe_params(c)["matmul"] * tokens + attn * tokens
