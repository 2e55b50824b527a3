"""The work a step needs, from its shapes.

These are the counts of what the computation needs, never of what one
implementation happens to do: a decode step reads every weight it uses
once, the K/V of the positions it attends to, and writes the new K/V; it
gathers only its tokens' rows of the embedding table.  A later program
that reads less raises the shares built on these counts; it does not change
them.  Sizes come from the configuration's hf keys.
"""

from __future__ import annotations

from typing import Dict, Sequence

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
NORM_BYTES = 4  # LayerNorm scale and bias are kept in float32


def _dims(hf: Dict):
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    hd = d // h
    return hf["num_hidden_layers"], d, h, hf["num_key_value_heads"], hd, \
        hf["intermediate_size"], hf["vocab_size"]


def layer_matmul_params(hf: Dict) -> int:
    """q, k, v, o projections and the SwiGLU MLP of one layer."""
    _, d, h, kv, hd, f, _ = _dims(hf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(hf: Dict) -> int:
    """Every weight a token multiplies: all layers and the output head."""
    L, d, *_, v = _dims(hf)
    return L * layer_matmul_params(hf) + v * d


def weight_bytes(hf: Dict) -> int:
    """Every parameter as served: matrices and tables in the served dtype,
    the LayerNorms in float32."""
    L, d, *_, v = _dims(hf)
    tables = v * d * (1 if hf["tie_word_embeddings"] else 2)
    matrices = L * layer_matmul_params(hf) + tables
    norms = (2 * L + 1) * 2 * d
    return matrices * _BYTES[hf["torch_dtype"]] + norms * NORM_BYTES


def kv_bytes_per_token(hf: Dict) -> int:
    L, _, _, kv, hd, _, _ = _dims(hf)
    return 2 * L * kv * hd * _BYTES[hf["torch_dtype"]]


def decode_flops(hf: Dict, position: int) -> int:
    """One token at ``position``: 2 per matmul weight, and per layer
    4 * heads * head_dim per attended position (scores and values)."""
    L, _, h, _, hd, _, _ = _dims(hf)
    return 2 * matmul_params(hf) + 4 * L * h * hd * (position + 1)


def decode_step_flops(hf: Dict, positions: Sequence[int]) -> int:
    return sum(decode_flops(hf, p) for p in positions)


def decode_step_bytes(hf: Dict, positions: Sequence[int]) -> int:
    """One step of a batch whose sessions sit at ``positions``: every
    weight once (of the embedding table, only the batch's rows), the K/V of
    positions 0..p read and the new K/V written, per session."""
    _, d, *_, v = _dims(hf)
    item = _BYTES[hf["torch_dtype"]]
    weights = weight_bytes(hf) - v * d * item + len(positions) * d * item
    kv = kv_bytes_per_token(hf)
    return weights + sum((p + 1) * kv + kv for p in positions)
