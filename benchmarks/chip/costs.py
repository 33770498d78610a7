"""The yardstick's arithmetic: chip peaks, and operations and bytes by shape.

Every number here is computed from shapes and the published peaks, never
read from the program under test, so no change to the program can move
it. Roofline shares and utilisations divide these by measured times.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(path.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table['devices'])}") from None


def matmul_cost(m: int, k: int, n: int, in_bytes: int, out_bytes: int
                ) -> tuple[float, float]:
    """(operations, bytes) of one (m, k) @ (k, n) product read and written once."""
    return 2.0 * m * k * n, float((m * k + k * n) * in_bytes + m * n * out_bytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict[str, float]
                     ) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


def gemm_job_flops(n: int) -> float:
    """Operations of C = A @ B at n x n: 2 n^3 (the block sums are n^2 (b-1))."""
    return 2.0 * n ** 3


def dense_lm_params(c: dict) -> dict[str, float]:
    """Parameter counts of a Llama-style decoder from its published sizes.

    ``c`` uses Hugging Face ``config.json`` keys. ``matmul`` counts every
    weight that a token meets in a matrix product: the layers' projections
    and the output head (the tied embedding, once), not the norm gains.
    """
    d, f, n_layers = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    hd = d // c["num_attention_heads"]
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    mlp = 3 * d * f
    layer = q + kv + o + mlp
    embed = c["vocab_size"] * d
    head = 0 if c["tie_word_embeddings"] else c["vocab_size"] * d
    norms = n_layers * 2 * d + d
    return {"layers": float(n_layers * layer), "embed": float(embed),
            "head": float(head), "norms": float(norms),
            "matmul": float(n_layers * layer + (head or embed)),
            "total": float(n_layers * layer + embed + head + norms)}


def dense_lm_train_flops(c: dict, batch: int, seq: int) -> float:
    """Model operations of one training step, forward and backward.

    6 N D for the weight products (N = ``matmul`` parameters, D = batch x
    seq tokens), plus 12 L H hd S D for attention's score and value
    products over the whole S x S square (the PaLM appendix B convention).
    Recomputation is not counted.
    """
    tokens = batch * seq
    n = dense_lm_params(c)["matmul"]
    hd = c["hidden_size"] // c["num_attention_heads"]
    attn = 12.0 * c["num_hidden_layers"] * c["num_attention_heads"] * hd * seq
    return 6.0 * n * tokens + attn * tokens
