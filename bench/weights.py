"""Seeded random weights of a dense decoder, made by the benchmark itself.

The program under test receives them in its own parameter layout; the
float32 reference regenerates the same values layer by layer, so neither
side takes weights from the other. Every leaf is a truncated normal of
standard deviation 0.02 (norm scales are ones), in bfloat16 as served.

A leaf's values depend only on (seed, leaf name, layer index): the stacked
leaves of the program are made by mapping the very function the reference
calls for one layer over the layer indices.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02

# per-layer leaves: reference name -> path in the program's parameter tree
LAYER_LEAVES = {
    "attn_norm": ("blocks", "b0", "norm1"),
    "wq": ("blocks", "b0", "mixer", "wq"),
    "wk": ("blocks", "b0", "mixer", "wk"),
    "wv": ("blocks", "b0", "mixer", "wv"),
    "wo": ("blocks", "b0", "mixer", "wo"),
    "mlp_norm": ("blocks", "b0", "norm2"),
    "gate": ("blocks", "b0", "ff", "gate"),
    "up": ("blocks", "b0", "ff", "up"),
    "down": ("blocks", "b0", "ff", "down"),
}
GLOBAL_LEAVES = {"embed": ("embed",), "final_norm": ("final_norm",),
                 "lm_head": ("lm_head",)}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(seed >> 32))


def layer_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    D, F = m["hidden_size"], m["intermediate_size"]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    return {"attn_norm": (D,), "wq": (D, H * hd), "wk": (D, K * hd),
            "wv": (D, K * hd), "wo": (H * hd, D), "mlp_norm": (D,),
            "gate": (D, F), "up": (D, F), "down": (F, D)}


def global_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    D, V = m["hidden_size"], m["vocab_size"]
    out = {"embed": (V, D), "final_norm": (D,)}
    if not m["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def _leaf(key, name: str, shape, dtype):
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, jnp.uint32(zlib.crc32(name.encode())))
    return (STD * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                              jnp.float32)).astype(dtype)


def layer_weights(key, layer, m: dict, dtype=jnp.bfloat16) -> dict:
    """One layer's weights {reference name: array}; ``layer`` may be traced."""
    lk = jax.random.fold_in(key, jnp.asarray(layer, jnp.uint32))
    return {n: _leaf(lk, n, s, dtype) for n, s in layer_shapes(m).items()}


def global_weights(key, m: dict, dtype=jnp.bfloat16) -> dict:
    gk = jax.random.fold_in(key, jnp.uint32(0xFFFFFFFF))
    return {n: _leaf(gk, n, s, dtype) for n, s in global_shapes(m).items()}


def _set(tree: dict, path: tuple[str, ...], value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def program_params(seed: int, m: dict) -> dict:
    """Every weight of the model in the program's layout, made on the device
    in one jitted call: ``{"embed", "final_norm", "lm_head"?, "blocks":
    {"b0": {...}}}`` with per-layer leaves stacked on a leading layer axis."""
    dtype = jnp.dtype(m["torch_dtype"])

    @jax.jit
    def make(key):
        stacked = jax.lax.map(lambda g: layer_weights(key, g, m, dtype),
                              jnp.arange(m["num_hidden_layers"]))
        tree: dict = {}
        for name, arr in global_weights(key, m, dtype).items():
            _set(tree, GLOBAL_LEAVES[name], arr)
        for name, arr in stacked.items():
            _set(tree, LAYER_LEAVES[name], arr)
        return tree

    return make(seed_key(seed))
