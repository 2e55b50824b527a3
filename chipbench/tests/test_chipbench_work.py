"""chipbench.work against numbers worked by hand for stablelm-3b."""

import json
from pathlib import Path

from chipbench import work

HF = json.loads((Path(__file__).resolve().parents[1] / "configs" / "stablelm-3b.json").read_text())


def test_weight_bytes_by_hand():
    # per layer 4 * 2560**2 (q, k, v, o) + 3 * 2560 * 6912 (SwiGLU) matmul
    # weights; 32 layers; embedding and head 50304 * 2560 each; all bf16.
    # LayerNorm scale + bias in float32: two per layer and the final one.
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert layer == 79_298_560
    bf16 = (32 * layer + 2 * 50304 * 2560) * 2
    norms = (2 * 32 + 1) * 2 * 2560 * 4
    assert work.weight_bytes(HF) == bf16 + norms == 5_591_552_000


def test_kv_bytes_per_token_by_hand():
    # K and V, 32 layers, 32 heads of 80, bf16
    assert work.kv_bytes_per_token(HF) == 2 * 32 * 32 * 80 * 2 == 327_680


def test_decode_flops_by_hand():
    matmul = 32 * 79_298_560 + 50304 * 2560  # the unembedding counts, the lookup not
    assert work.matmul_params(HF) == matmul
    # at position 9 attention reads 10 positions: 4 * 32 layers * 2560 * 10
    assert work.decode_flops(HF, 9) == 2 * matmul + 4 * 32 * 2560 * 10
    assert work.decode_step_flops(HF, [0, 9]) == work.decode_flops(HF, 0) + work.decode_flops(HF, 9)


def test_decode_step_bytes_by_hand():
    # batch 2 at positions 0 and 3: weights without the unread embedding
    # rows, two gathered rows, K/V of 1 and 4 positions read, 2 written
    weights = 5_591_552_000 - 50304 * 2560 * 2 + 2 * 2560 * 2
    kv = 327_680 * (1 + 4) + 2 * 327_680
    assert work.decode_step_bytes(HF, [0, 3]) == weights + kv
