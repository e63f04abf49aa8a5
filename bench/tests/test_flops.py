"""Operation and byte counts against hand counts at published widths."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops as F

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def raw(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# the matmul widths of Qwen/Qwen3-8B's published config.json
QWEN3_8B = {"hidden_size": 4096, "intermediate_size": 12288,
            "num_hidden_layers": 36, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}


def test_qwen2_matmul_params():
    # per layer: q 1536x1536, k and v 1536x256, o 1536x1536, gate|up
    # 1536x17920, down 8960x1536; 28 layers
    per_layer = (1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536
                 + 1536 * 17920 + 8960 * 1536)
    assert per_layer == 46_792_704
    assert F.matmul_params(raw("qwen2-1.5b")) == 1_310_195_712


def test_qwen3_matmul_params():
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate|up 4096x24576, down 12288x4096; 36 layers
    per_layer = (2 * 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 24576
                 + 12288 * 4096)
    assert per_layer == 192_937_984
    assert F.matmul_params(QWEN3_8B) == 6_945_767_424


@pytest.mark.parametrize("config,per_token", [(raw("qwen2-1.5b"), 28_672),
                                              (QWEN3_8B, 147_456)])
def test_kv_bytes_per_token(config, per_token):
    # layers x (K and V) x KV heads x head dim x 2 bytes
    assert F.kv_bytes_per_token(config) == per_token


def test_call_work_counts_real_tokens_only():
    r = raw("qwen2-1.5b")
    pos = np.full((4, 8), -1)
    pos[1, :3] = [10, 11, 12]       # a prefill chunk of 3 at 10..12
    pos[2, 0] = 99                  # one decode token at 99
    w = F.call_work(r, pos)
    assert (w["tokens"], w["rows"]) == (4, 2)
    head = 1536 * 151936
    assert w["gemm_flops"] == 2.0 * (1_310_195_712 * 4 + head * 2)
    # attention: 4 x layers x heads x head dim x context, per real token
    assert w["attn_flops"] == 4.0 * 28 * 12 * 128 * (11 + 12 + 13 + 100)
    # K/V read over each row's context (13 and 100) plus the 4 written
    kv = 28_672 * (13 + 100 + 4)
    assert w["attn_bytes"] == kv + 2 * 2 * 28 * 12 * 128 * 4


def test_least_seconds_takes_the_binding_bound():
    assert F.least_seconds(197e12, 0, 197e12, 819e9) == 1.0
    assert F.least_seconds(0, 819e9, 197e12, 819e9) == 1.0


def test_a_family_count_takes_the_place_of_the_dense_one():
    fam = SimpleNamespace(layer_matmuls=lambda r: [(10, 20), (20, 10)],
                          kv_bytes_per_token=lambda r, itemsize: 7)
    r = raw("qwen2-1.5b")
    assert F.matmul_params(r, fam) == 28 * 400
    assert F.kv_bytes_per_token(r, family=fam) == 7
    pos = np.full((2, 4), -1)
    pos[0, :2] = [5, 6]
    w = F.call_work(r, pos, family=fam)
    assert w["gemm_flops"] == 2.0 * (28 * 400 * 2 + 1536 * 151936 * 1)
    # K/V read over the row's context of 7 plus the 2 written
    assert w["attn_bytes"] == 7 * (7 + 2) + 2 * 2 * 28 * 12 * 128 * 2
