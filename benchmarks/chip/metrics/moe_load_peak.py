"""The most rows any held expert took in one MoE layer's step, over the
rows an expert takes when routing is even (the step's tokens times
``num_experts_per_tok / router_experts``), across the window's jobs. Read
from the step's ``moe_load_max`` counter. Moves ``train_tokens_per_s``:
the most loaded expert sets an expert-parallel layer's pace."""


def read(run):
    loads = [j.info["moe_load_max"] for j in run.window.jobs if "moe_load_max" in j.info]
    if not loads or "moe_rows_per_expert" not in run.work:
        return None
    return max(loads) / run.work["moe_rows_per_expert"]
