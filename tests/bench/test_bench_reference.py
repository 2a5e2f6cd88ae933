"""The benchmark's float32 reference against the program's model at a small
width on the CPU, and the benchmark's weights against their per-layer
regeneration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense


def _dims(**kw):
    m = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=300, rope_theta=10000.0, rms_norm_eps=1e-5,
             tie_word_embeddings=False, torch_dtype="float32")
    m.update(kw)
    return m


def _program_logits(m, seed, toks, positions):
    from bench.runners.serve_offline import program_config
    from repro.models import init_caches, prefill

    cfg = program_config("t", m)
    params = weights.program_params(seed, m)
    out = []
    for p in positions:
        caches = init_caches(cfg, toks.shape[0], toks.shape[1])
        lg, _ = prefill(params, {"tokens": jnp.asarray(toks)}, cfg, caches,
                        last_index=jnp.full((toks.shape[0],), p, jnp.int32))
        out.append(np.asarray(lg))
    return np.stack(out, 1)                     # [n, P, V]


@pytest.mark.parametrize("kw", [
    {},                                              # GQA 2:1, untied head
    {"num_key_value_heads": 4},                      # MHA
    {"tie_word_embeddings": True, "rope_theta": 1e6},
])
def test_reference_matches_program_model_in_float32(kw):
    m = _dims(**kw)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (2, 24)).astype(np.int32)
    pos = [0, 7, 23]
    got = _program_logits(m, 3, toks, pos)
    ref = np.asarray(dense.logits_at(3, m, toks, np.tile(pos, (2, 1))))
    scale = np.abs(ref).max()
    assert scale > 0.05
    np.testing.assert_allclose(got, ref, atol=2e-4 * scale, rtol=0)


def test_stacked_weights_equal_their_per_layer_regeneration():
    m = _dims(torch_dtype="bfloat16")
    p = weights.program_params(2 ** 40 + 3, m)
    key = weights.seed_key(2 ** 40 + 3)
    for g in range(m["num_hidden_layers"]):
        one = weights.layer_weights(key, jnp.uint32(g), m, jnp.bfloat16)
        for name, path in weights.LAYER_LEAVES.items():
            leaf = p
            for k in path:
                leaf = leaf[k]
            np.testing.assert_array_equal(np.asarray(leaf[g]),
                                          np.asarray(one[name]))
    glob = weights.global_weights(key, m, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(p["embed"]),
                                  np.asarray(glob["embed"]))
    assert p["embed"].dtype == jnp.bfloat16
    # another seed, other weights
    q = weights.program_params(2 ** 40 + 4, m)
    assert not np.array_equal(np.asarray(p["embed"]), np.asarray(q["embed"]))


def test_control_departs_from_float32():
    m = _dims()
    toks = np.arange(40, dtype=np.int32).reshape(2, 20)
    read = np.tile(np.arange(20), (2, 1))
    ref = np.asarray(dense.logits_at(1, m, toks, read))
    low = np.asarray(dense.logits_at(1, m, toks, read, precision=dense.CONTROL))
    rel = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < rel < 0.5


def test_seed_range():
    with pytest.raises(ValueError):
        weights.seed_key(-1)
    assert weights.seed_key(2 ** 63).shape == jax.random.PRNGKey(0).shape
