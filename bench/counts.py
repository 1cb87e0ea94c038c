"""Operations and bytes that the benchmarked work needs, from its shapes.

These are defined by the work itself, not by what today's kernels touch, so
a kernel that does less work is credited and a removed kernel still leaves
the step's utilization bounding a claim. Every function takes the model
configuration file's keys (Hugging Face names) as a plain dict.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """Peaks of one chip of ``device_kind``; an unknown device is an error."""
    table = json.loads(Path(path).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table['devices'])}") from None


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_params(c: dict) -> int:
    """Parameters of one decoder layer: attention (with q/k/v biases where
    ``attention_bias``), SwiGLU MLP and two RMSNorm scales."""
    d, hd = c["hidden_size"], head_dim(c)
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    if c.get("attention_bias", True):
        attn += q + 2 * kv
    return attn + 3 * d * c["intermediate_size"] + 2 * d


def param_count(c: dict) -> int:
    """All parameters: embedding, layers, final norm, and an untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    head = 0 if c["tie_word_embeddings"] else d * v
    return v * d + c["num_hidden_layers"] * layer_params(c) + d + head


def matmul_params(c: dict) -> int:
    """Parameters that a token meets in a matrix product: every layer matrix
    and the output head (the embedding lookup is a gather, not a product)."""
    d, hd = c["hidden_size"], head_dim(c)
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def attn_flops_per_token(c: dict, ctx: float) -> float:
    """Score and value products of one token against ``ctx`` keys, all
    layers: 2 products x 2 FLOPs x heads x head_dim x ctx."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * head_dim(c) * ctx)


def forward_flops_per_token(c: dict, ctx: float) -> float:
    return 2.0 * matmul_params(c) + attn_flops_per_token(c, ctx)


def train_flops_per_token(c: dict, seq: int, passes: int = 2) -> float:
    """Forward and backward (3x forward) at the causal mean context
    (seq + 1) / 2, ``passes`` times per token: mu^2-SGD evaluates the
    gradient at x_t and at x_{t-1} on the same sample. Recomputation for
    memory does not count."""
    return passes * 3.0 * forward_flops_per_token(c, (seq + 1) / 2.0)


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """Key and value bytes one cached token holds over all layers."""
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * head_dim(c) * dtype_bytes)


def ragged_attn_bytes(c: dict, rows: list, dtype_bytes: int = 2) -> int:
    """Least bytes paged attention moves for one call over ``rows``, a list
    of (query tokens, kv_len): every cached key and value that a row
    attends to read once per layer, and each query and output written once."""
    per_kv = kv_bytes_per_token(c, dtype_bytes)
    q = c["num_hidden_layers"] * c["num_attention_heads"] * head_dim(c) * dtype_bytes
    return sum(kv * per_kv + 2 * n * q for n, kv in rows)


def agg_one_read_bytes(m: int, d: int, dtype_bytes: int = 4) -> int:
    """Lower bound of a robust aggregate over an (m, d) matrix: one read of
    it and one write of the (d,) result."""
    return (m + 1) * d * dtype_bytes


def serve_row_flops(c: dict, n: int, kv: int) -> float:
    """FLOPs one row of a serving step needs: ``n`` new tokens whose last
    one attends to ``kv`` keys through every layer's matrices and attention,
    and the output head once, for the row's last token."""
    d, hd = c["hidden_size"], head_dim(c)
    layer_mm = matmul_params(c) - d * c["vocab_size"]
    ctx = n * kv - n * (n - 1) / 2.0            # keys seen by the n tokens
    return (2.0 * layer_mm * n + 2.0 * d * c["vocab_size"]
            + attn_flops_per_token(c, 1.0) * ctx)
