"""Model operations and bytes, checked against hand counts at the smoke
size of the SmolLM configurations."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from chipbench import model_ops  # noqa: E402

SMOKE = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
         "tie_word_embeddings": True, "butterfly": None}
BFLY = dict(SMOKE, tie_word_embeddings=False,
            butterfly={"sites": ["lm_head", "mlp"], "k_factor": 1.0,
                       "seed": 0})


def test_dense_forward_flops_by_hand():
    # per layer: qkv 2*64*(4+2+2)*16 + out 2*64*64 + mlp 3 * 2*64*128
    layer = 2 * 64 * 128 + 2 * 64 * 64 + 3 * 2 * 64 * 128
    head = 2 * 64 * 512
    attn = 4 * 4 * 16 * 10          # 4 H D ctx, ctx = 10
    assert model_ops.forward_flops_per_token(SMOKE, 10) == \
        2 * (layer + attn) + head


def test_sandwich_flops_by_hand():
    # 64 -> 128: N1 = 64 (6 stages), N2 = 128 (7 stages), k = 6 and 7
    assert model_ops.sandwich_flops(64, 128, 1.0) == \
        3 * 64 * 6 + 2 * 6 * 7 + 3 * 128 * 7
    # 49152 pads to 65536 (16 stages); k = round(log2 49152) = 16
    assert model_ops.sandwich_flops(576, 49152, 1.0) == \
        3 * 1024 * 10 + 2 * 9 * 16 + 3 * 65536 * 16


def test_butterfly_forward_flops_by_hand():
    up = model_ops.sandwich_flops(64, 128, 1.0)
    down = model_ops.sandwich_flops(128, 64, 1.0)
    head = model_ops.sandwich_flops(64, 512, 1.0)
    layer = 2 * 64 * 128 + 2 * 64 * 64 + 2 * up + down
    assert model_ops.forward_flops_per_token(BFLY, 0) == 2 * layer + head
    assert model_ops.sandwich_row_flops(BFLY) == 2 * (2 * up + down) + head


def test_weights_and_bytes_by_hand():
    dense_layer = 64 * 128 + 64 * 64 + 3 * 64 * 128
    assert model_ops.weight_count(SMOKE) == 2 * dense_layer + 512 * 64
    assert model_ops.kv_bytes_per_position(SMOKE) == 2 * 2 * 2 * 16 * 2
    # each MLP sandwich row moves its bf16 input and output once
    assert model_ops.sandwich_row_bytes(BFLY) == \
        2 * (2 * (64 + 128) + (128 + 64)) * 2 + (64 + 512) * 2


def test_train_flops_count_forward_and_backward():
    assert model_ops.train_flops_per_token(SMOKE, 7) == pytest.approx(
        3 * model_ops.forward_flops_per_token(SMOKE, 4.0))
