"""Operation and byte counts of the benchmark's yardstick at known shapes."""
import json
from pathlib import Path

import pytest

from bench import harness, work

ROOT = Path(__file__).resolve().parents[2]


# Mistral-7B-v0.3's published config.json, for counts at a second geometry
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_hidden_layers": 32, "vocab_size": 32768, "rope_theta": 1e6,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
           "torch_dtype": "bfloat16"}


def _dims(name):
    if name == "mistral-7b":
        return harness.model_dims(MISTRAL)
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return harness.model_dims(cfg)


def _params(m):
    D, V = m["hidden_size"], m["vocab_size"]
    emb = D * V * (1 if m["tie_word_embeddings"] else 2)
    return (m["num_hidden_layers"] * (work.layer_matmul_params(m) + 2 * D)
            + emb + D)


@pytest.mark.parametrize("name,layers,params", [
    ("phi3-mini-4k", 32, 3_821_079_552),    # the published model's size
    ("mistral-7b", 32, 7_248_023_552),      # the published Mistral-7B
    ("mistral-7b", 16, 3_758_231_552),      # a 16-layer pipeline stage
])
def test_parameter_counts(name, layers, params):
    assert _params(dict(_dims(name), num_hidden_layers=layers)) == params


@pytest.mark.parametrize("name,per_token", [
    ("phi3-mini-4k", 204_800),   # 32 layers x 2 x 32 heads x (96 + 4) B
    ("mistral-7b", 67_584),      # 32 layers x 2 x 8 heads x (128 + 4) B
])
def test_kv_bytes_per_token_at_8_bits(name, per_token):
    assert work.kv_bytes_per_token(_dims(name), 8) == per_token


def test_flops_at_a_small_shape():
    m = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
         "vocab_size": 10}
    layer = 8 * 4 * 4 + 8 * 8 + 3 * 8 * 16            # qkv + o + mlp
    assert work.layer_matmul_params(m) == layer
    assert work.attention_flops(m, 5) == 4 * 3 * 2 * 4 * 5
    assert work.decode_flops(m, 5) == 2 * (3 * layer + 80) + 480
    assert work.prefill_flops(m, 4) == (2 * 4 * 3 * layer + 2 * 80
                                        + 4 * 3 * 2 * 4 * 10)
    assert work.request_flops(m, 4, 3) == (work.prefill_flops(m, 4)
                                           + work.decode_flops(m, 5)
                                           + work.decode_flops(m, 6))


def test_attention_paged_work():
    m = _dims("phi3-mini-4k")
    ops, nbytes = work.attention_paged_work(m, kv_tokens=1000, queries=16,
                                            n_bits=8)
    assert ops == 4 * 32 * 32 * 96 * 1000
    assert nbytes == 1000 * 204_800 + 2 * 16 * 32 * 96 * 2 * 32


def test_peaks_known_and_unknown_kinds():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
