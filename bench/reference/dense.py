"""Float32 reference of a dense decoder from its published equations.

RMSNorm, rotary embeddings on the two halves of each head, grouped-query
causal softmax attention, a SwiGLU MLP and an untied or tied LM head, as in
the Hugging Face ``LlamaForCausalLM`` / ``MistralForCausalLM`` /
``Phi3ForCausalLM`` modelling code (Phi-3's fused qkv and gate_up
projections are the same maps split differently). Plain ``jax.numpy`` with
every matmul at ``Precision.HIGHEST``; no kernel, cache or batching of the
program under test. Weights come from :mod:`bench.weights` layer by layer,
so the model never has to fit on the device at once.

``precision="fp8_kv4"`` is the control, the model computed one step below
each precision the configurations state. For their bfloat16 weights and
activations, float8 e4m3: every matmul operand under a per-tensor scale,
and the activations that flow between layers (the embedding output and the
residual stream after each sublayer) under a per-token scale. For their
8-bit KV cache, 4 bits: keys (after rotation) and values rounded to the
integers -7..7 under one scale per token and head. Norms, softmax and
accumulation stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
CONTROL = "fp8_kv4"


def _fp8(x, axis=None):
    """Round to float8 e4m3 under one scale per slice along ``axis`` (one
    per tensor when None), back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _act(x, precision):
    """An activation passed between layers, in the control's precision."""
    return _fp8(x, axis=-1) if precision == CONTROL else x


def _kv(x, precision):
    """A cached key or value [S, K, hd], in the control's precision."""
    if precision != CONTROL:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 7
    return jnp.round(x / s) * s


def _mm(spec, a, b, precision):
    if precision == CONTROL:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, w, m, precision):
    """x [S, D] one sequence (normed); causal GQA over its own positions."""
    S = x.shape[0]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    pos = jnp.arange(S)
    q = _rope(_mm("sd,dh->sh", x, w["wq"], precision).reshape(S, H, hd),
              pos, m["rope_theta"])
    k = _kv(_rope(_mm("sd,dh->sh", x, w["wk"], precision).reshape(
        S, K, hd), pos, m["rope_theta"]), precision)
    v = _kv(_mm("sd,dh->sh", x, w["wv"], precision).reshape(S, K, hd),
            precision)
    q = q.reshape(S, K, H // K, hd)
    s = _mm("qkgd,skd->kgqs", q, k, precision) / np.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("kgqs,skd->qkgd", p, v, precision).reshape(S, H * hd)
    return _mm("sh,hd->sd", o, w["wo"], precision)


@functools.partial(jax.jit, static_argnames=("mj", "precision"))
def _layer(x, key, layer, mj, precision):
    m = dict(mj)
    w = {n: a.astype(jnp.float32)
         for n, a in W.layer_weights(key, layer, m,
                                     jnp.dtype(m["torch_dtype"])).items()}
    eps = m["rms_norm_eps"]

    def one(xs):
        h = _rms(xs, w["attn_norm"], eps)
        xs = _act(xs + _attention(h, w, m, precision), precision)
        h = _rms(xs, w["mlp_norm"], eps)
        g = _mm("sd,df->sf", h, w["gate"], precision)
        u = _mm("sd,df->sf", h, w["up"], precision)
        return _act(xs + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["down"],
                             precision), precision)

    return jax.lax.map(one, x)


def _globals(key, m):
    return {n: a.astype(jnp.float32)
            for n, a in W.global_weights(key, m,
                                         jnp.dtype(m["torch_dtype"])).items()}


@functools.partial(jax.jit, static_argnames=("mj", "precision"))
def _embed(tokens, key, mj, precision):
    emb = _globals(key, dict(mj))["embed"]
    return _act(jnp.take(emb, tokens, axis=0), precision)


@functools.partial(jax.jit, static_argnames=("mj", "precision"))
def _head(x, read, key, mj, precision):
    m = dict(mj)
    g = _globals(key, m)
    head = g["embed"].T if m["tie_word_embeddings"] else g["lm_head"]
    h = _rms(x, g["final_norm"], m["rms_norm_eps"])
    h = jnp.take_along_axis(h, read[..., None], axis=1)      # [n, P, D]
    return _mm("npd,dv->npv", h, head, precision)


def _frozen(m: dict):
    return tuple(sorted(m.items()))


def logits_at(seed: int, m: dict, tokens, read, precision: str = "f32"):
    """Reference logits [n, P, vocab] (float32) of ``tokens`` [n, S] read at
    positions ``read`` [n, P]: the weights of ``seed``, one layer at a time
    over all n sequences."""
    key = W.seed_key(seed)
    mj = _frozen(m)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(tokens, key, mj, precision)
    for layer in range(m["num_hidden_layers"]):
        x = _layer(x, key, jnp.uint32(layer), mj, precision)
    return _head(x, jnp.asarray(read, jnp.int32), key, mj, precision)
