"""The algorithmic counts, against the program's parameter count and
against values worked by hand at one small shape."""
from __future__ import annotations

import pytest

from conftest import ROOT

CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
SMALL_LLAMA = {
    "num_hidden_layers": 1, "hidden_size": 4, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8, "vocab_size": 16,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
}


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    from bench.common import BENCH, load_json, load_module
    from repro.launch.analytic import exact_param_counts

    c = load_json(ROOT / "bench" / "configs" / f"{name}.json")
    ref = load_module(BENCH / "models" / f"{c['reference']}.py")
    assert ref.param_count(c) == exact_param_counts(ref.program_config(c))["total"]


def test_llama_counts_by_hand():
    from bench.common import BENCH, load_module

    ref = load_module(BENCH / "models" / "llama.py")
    c = SMALL_LLAMA
    # per layer: q,k,v 4*(2+1+1)*2 = 32, o 2*2*4 = 16, mlp 3*4*8 = 96; head 4*16
    assert ref.matmul_params(c) == 32 + 16 + 96 + 64
    # causal attention over 3 tokens: 1+2+3 = 6 query-key pairs, each
    # 2 (QK) + 2 (PV) multiply-adds of width 2, per head, 2 heads
    assert ref.attn_flops(c, 1) * 6 == 6 * 2 * 2 * 2 * 2
    assert ref.prefill_flops(c, 3) == 2 * 208 * 3 + 96
    # one decode token at position 4 attends 5 keys
    assert ref.decode_flops(c, 4) == 2 * 208 + 4 * 1 * 2 * 2 * 5
    # params: embed 64, ln 4+4, q 16, k 8, v 8, o 16, mlp 96, final 4 = 220
    assert ref.param_count(c) == 220
    # K and V, 1 layer, 1 kv head of 2, bf16: 8 bytes a token; slots at
    # positions 3 and 5 read 4 and 6 entries with the new one written
    assert ref.kv_bytes_per_token(c) == 8
    assert ref.decode_step_bytes(c, [3, 5]) == 440 + (4 + 6) * 8
    # training: three forwards per token of a 4-token row
    assert ref.train_flops_per_token(c, 4) == 3 * ref.prefill_flops(c, 4) / 4


def test_rwkv_counts_by_hand():
    from bench.common import BENCH, load_module

    ref = load_module(BENCH / "models" / "rwkv6.py")
    c = {"num_hidden_layers": 2, "hidden_size": 8, "head_size": 4, "intermediate_size": 16,
         "vocab_size": 32, "layer_norm_epsilon": 1e-5}
    # per layer: r,k,v,g,w,o 6*64, channel mix 2*8*16 + 64; head 8*32
    assert ref.matmul_params(c) == 2 * (6 * 64 + 256 + 64) + 256
    # WKV per token: 2 heads x (2 x 4 x 4 for r^T S, 2 x 4 x 4 for the update), 2 layers
    assert ref.wkv_flops_per_token(c) == 2 * 2 * 64
    assert ref.train_flops_per_token(c, 7) == 3 * (2 * ref.matmul_params(c) + 256)
