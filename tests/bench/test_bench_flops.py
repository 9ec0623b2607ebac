"""Operation counts against hand counts of both configurations."""

import json
from pathlib import Path

import pytest

from bench import flops

ROOT = Path(__file__).resolve().parents[2]

QWEN3_ONE_LAYER = {       # qwen3-235b-a22b's published widths, one layer
    "hidden_size": 4096, "num_hidden_layers": 1, "num_attention_heads": 64,
    "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 151936,
    "intermediate_size": 12288, "num_experts": 128,
    "num_experts_per_tok": 8, "moe_intermediate_size": 1536}


def test_qwen3_one_layer_hand_count():
    d = flops.dims(QWEN3_ONE_LAYER)
    attn = 4096 * 8192 + 2 * 4096 * 512 + 8192 * 4096     # 71.3M
    router = 4096 * 128                                    # 0.5M
    experts = 8 * 3 * 4096 * 1536                          # 151M
    head = 151936 * 4096                                   # 622M
    assert attn == 71_303_168 and experts == 150_994_944
    assert flops.linear_flops(d) == 2 * (attn + router + experts + head)
    assert flops.linear_flops(d, head=False) == 2 * (attn + router
                                                     + experts)
    # Position p attends p + 1 keys: q.k and p.v, 2 operations each.
    assert flops.attn_flops(d, 0, 1) == 4 * 64 * 128
    assert flops.attn_flops(d, 10, 1) == 11 * 4 * 64 * 128
    assert flops.attn_flops(d, 0, 4096) == 4 * 64 * 128 * 4096 * 4097 // 2


def test_glm_air_hand_count():
    c = json.loads((ROOT / "bench/configs/glm-4.5-air.json").read_text())
    d = flops.dims(c)
    assert d.kinds == ("dense", "moe")
    attn = 4096 * 12288 + 2 * 4096 * 1024 + 12288 * 4096   # q, k and v, o
    dense = 3 * 4096 * 10944
    moe = 4096 * 128 + 8 * 3 * 4096 * 1408 + 3 * 4096 * 1408
    head = 151552 * 4096
    per_token = 2 * (2 * attn + dense + moe + head)
    assert flops.linear_flops(d) == per_token
    # About 1.13 B active parameters per token, the head included.
    assert per_token / 2 == pytest.approx(1.13e9, rel=0.01)


def test_span_counts_head_only_where_asked():
    d = flops.dims(QWEN3_ONE_LAYER)
    one = flops.span_flops(d, 0, 512, head_tokens=1)
    full = flops.span_flops(d, 0, 512, head_tokens=512)
    assert full - one == 511 * 2 * 151936 * 4096
    # A chunk later in the prompt attends more keys.
    assert flops.span_flops(d, 512, 512, head_tokens=0) > flops.span_flops(
        d, 0, 512, head_tokens=0)


def test_peaks_table_names_its_source():
    table = json.loads((ROOT / "bench/peaks.json").read_text())
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
