"""Rows the held experts' grouped products computed, per token and MoE
layer, over the window's jobs: the step's ``moe_held_rows`` counter over
the tokens that passed through MoE layers. Even routing gives
``num_experts_per_tok x n_routed_experts / router_experts``; padding added
by the dispatch would show above it. Moves ``train_tokens_per_s``."""


def read(run):
    rows = [j.info["moe_held_rows"] for j in run.window.jobs if "moe_held_rows" in j.info]
    if not rows or "moe_layer_tokens" not in run.work:
        return None
    return sum(rows) / (len(rows) * run.work["moe_layer_tokens"])
