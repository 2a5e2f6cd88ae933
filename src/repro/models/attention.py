"""GQA attention: naive and chunked (online-softmax) implementations, KV cache
(bf16 or F2P8-quantized), RoPE, cross-attention.

Shapes: x [B, S, D]; q [B, S, H, hd]; k/v [B, S, K, hd] with H % K == 0.
Cache: dict with "k"/"v" leaves — either plain [B, Smax, K, hd] arrays (bf16
path) or :class:`repro.core.qtensor.QTensor` values (F2P8 path: uint8 codes
[B, Smax, K, hd] + per-(position, head) f32 scales [B, Smax, K, 1], i.e. the
canonical last-axis-blocked QTensor layout with block = head_dim). QTensor is
a registered pytree, so the quantized cache jits/scans/shards exactly like
the dense one; writes go through ``QTensor.dynamic_update`` which updates
codes and scales coherently. With ``packed=True`` (DESIGN.md §9) the codes
leaf holds bit-packed uint32 words — block = head_dim means every token's
codes are whole rows, so slab writes never straddle a word boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import qtensor as QT
from repro.core.f2p import F2PFormat, Flavor
from repro.core.qtensor import QTensor
from repro.kernels.f2p_attention import attention_packed, attention_paged
from repro.models.common import apply_rope, truncnorm_init

KV_FMT = F2PFormat(n_bits=8, h_bits=2, flavor=Flavor.SR, signed=True)


def init_attention(key, cfg, cross: bool = False):
    D, hd, H, K = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.jnp_dtype
    return {"wq": truncnorm_init(ks[0], (D, H * hd), dt),
            "wk": truncnorm_init(ks[1], (D, K * hd), dt),
            "wv": truncnorm_init(ks[2], (D, K * hd), dt),
            "wo": truncnorm_init(ks[3], (H * hd, D), dt)}


# ---------------------------------------------------------------------------
# KV quantization (per-(position, head) scale over the head_dim axis ==
# canonical QTensor blocking with block = head_dim). The format is per-cache:
# ``init_cache(..., fmt=...)`` takes the policy-chosen format for its layer
# (repro.autotune.policy via models.init_caches(kv_policy=...)); writes read
# the format back off the live cache QTensor, so mixed-format stacks need no
# extra plumbing.
# ---------------------------------------------------------------------------
def quantize_kv(k, fmt: F2PFormat = KV_FMT, packed: bool = False) -> QTensor:
    return QT.quantize(k, fmt, block=k.shape[-1], packed=packed)


def dequantize_kv(qt: QTensor, dtype):
    return qt.dequantize(dtype)


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------
def _broadcast_kv(k, H):
    """[B,S,K,hd] -> [B,S,H,hd] by repeating each KV head H//K times.

    Used by the head-sharded attention path (cfg.opt_head_shard): with a
    single merged head axis GSPMD can shard heads (padding 24->32 when the
    axis doesn't divide) instead of sharding head_dim and all-reducing the
    full [Sq,Sk] score tensors."""
    B, S, K, hd = k.shape
    G = H // K
    return jnp.broadcast_to(k[:, :, :, None], (B, S, K, G, hd)).reshape(
        B, S, H, hd)


def _len_mask(Sk: int, kv_len):
    """Additive 0/-inf mask over cache positions >= kv_len. Scalar kv_len ->
    ``[Sk]``; per-batch ``[B]`` kv_len (continuous batching: each slot has
    its own live length) -> ``[B, Sk]``."""
    kl = jnp.asarray(kv_len)
    return jnp.where(jnp.arange(Sk) < kl[..., None], 0.0, -jnp.inf)


def _mha_attention(q, k, v, *, causal, q_offset=0, kv_len=None):
    """Head-sharded attention: q/k/v all [B,S,H,hd], head axis constrained to
    the model mesh axis; scores stay device-local."""
    from repro.models.sharding import constrain

    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "heads", None))
    v = constrain(v, ("batch", None, "heads", None))
    Sq, Sk = q.shape[1], k.shape[1]
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    scores = constrain(scores / jnp.sqrt(hd), ("batch", "heads", None, None))
    mask = jnp.zeros((Sq, Sk), jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        mask = jnp.where(jnp.arange(Sk)[None, :] <= qpos, 0.0, -jnp.inf)
    if kv_len is not None:
        lm = _len_mask(Sk, kv_len)
        # scalar: [Sk] folds into the [Sq,Sk] mask; per-batch: [B,Sk] lifts
        # the mask to [B,1,Sq,Sk] against scores [B,H,Sq,Sk]
        mask = mask + lm if lm.ndim == 1 else mask + lm[:, None, None, :]
    probs = jax.nn.softmax(scores + mask, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return constrain(out, ("batch", None, "heads", None))


def _mha_chunked(q, k, v, *, causal, chunk, q_offset=0, kv_len=None):
    """Head-sharded online-softmax attention over KV chunks."""
    from repro.models.sharding import constrain

    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "heads", None))
    v = constrain(v, ("batch", None, "heads", None))
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    nchunk = -(-Sk // chunk)
    pad = nchunk * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunk, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        acc, m, l = carry
        ci, (kb, vb) = inp
        s = jnp.einsum("bqhd,bshd->bhqs", q, kb).astype(jnp.float32)
        s = s / jnp.sqrt(hd)
        kpos = ci * chunk + jnp.arange(chunk)
        valid = kpos[None, :] < (Sk if kv_len is None else kv_len)
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(valid[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(q.dtype), vb)
        acc_new = acc * corr[..., None].astype(q.dtype) + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, H, Sq, hd), q.dtype)
    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (jnp.arange(nchunk), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-37)[..., None].astype(q.dtype)
    return out.transpose(0, 2, 1, 3)


def _gqa_scores(q, k):
    """q [B,Sq,H,hd], k [B,Sk,K,hd] -> scores [B,K,G,Sq,Sk] (H = K*G)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    qg = q.reshape(B, Sq, K, H // K, hd)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(hd).astype(q.dtype)


def _gqa_out(probs, v):
    """probs [B,K,G,Sq,Sk], v [B,Sk,K,hd] -> [B,Sq,H,hd]."""
    B, K, G, Sq, _ = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, K * G, -1)


def naive_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None):
    """Full-materialization attention (reference; O(Sq*Sk) memory)."""
    Sq, Sk = q.shape[1], k.shape[1]
    scores = _gqa_scores(q, k).astype(jnp.float32)
    mask = jnp.zeros((Sq, Sk), jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Sk)[None, :]
        mask = jnp.where(kpos <= qpos, 0.0, -jnp.inf)
    if kv_len is not None:  # decode: only first kv_len cache slots valid
        lm = _len_mask(Sk, kv_len)
        # scalar: [Sk]; per-batch [B,Sk] lifts to [B,1,1,Sq,Sk] against the
        # GQA scores [B,K,G,Sq,Sk]
        mask = (mask + lm if lm.ndim == 1
                else mask + lm[:, None, None, None, :])
    probs = jax.nn.softmax(scores + mask, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v)


def chunked_attention(q, k, v, *, causal: bool, chunk: int, q_offset=0,
                      kv_len=None):
    """Online-softmax attention over KV chunks: O(Sq*chunk) live memory.
    Matches naive_attention numerically (f32 accumulation)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    G = H // K
    nchunk = -(-Sk // chunk)
    pad = nchunk * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunk, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, chunk, K, hd).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, Sq, K, G, hd)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        acc, m, l = carry
        ci, (kb, vb) = inp
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb).astype(jnp.float32)
        scores = scores / jnp.sqrt(hd)
        kpos = ci * chunk + jnp.arange(chunk)
        valid = kpos[None, :] < (Sk if kv_len is None else kv_len)
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        scores = jnp.where(valid[None, None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), vb)
        acc_new = acc * corr[..., None].astype(q.dtype) + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, K, G, Sq, hd), q.dtype)
    m0 = jnp.full((B, K, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (jnp.arange(nchunk), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-37)[..., None].astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Block-level apply
# ---------------------------------------------------------------------------
def attention_apply(params, x, cfg, *, mode: str, cache=None, pos_offset=0,
                    cross_kv=None, causal=True, pages=None):
    """mode: 'train' | 'prefill' | 'decode'. Returns (out, new_cache).

    ``pages`` (decode only): a ``[B, max_pages]`` int32 page table. When set,
    ``cache`` is a pool SLAB (``{"k","v"}`` QTensors, codes
    ``[n_pages, page_tokens, K*words]``) instead of a dense per-row cache:
    the new token's KV is quantized and scattered into the slab page holding
    position ``pos_offset`` and attention reads word tiles straight through
    the table (``attention_paged``) — no dense ``[B, max_seq]`` row exists
    anywhere in the decode path."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"]).reshape(B, S, H, hd)

    if cross_kv is not None:  # cross attention: kv from encoder output
        k = jnp.einsum("bsd,dh->bsh", cross_kv, params["wk"]).reshape(
            B, cross_kv.shape[1], K, hd)
        v = jnp.einsum("bsd,dh->bsh", cross_kv, params["wv"]).reshape(
            B, cross_kv.shape[1], K, hd)
        out = _attend(q, k, v, cfg, causal=False)
        proj = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd), params["wo"])
        return proj, cache

    k = jnp.einsum("bsd,dh->bsh", x, params["wk"]).reshape(B, S, K, hd)
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"]).reshape(B, S, K, hd)
    if cfg.pos == "rope":
        if jnp.ndim(pos_offset):        # per-slot offsets [B] -> [B, S]
            positions = jnp.asarray(pos_offset)[:, None] + jnp.arange(S)
        else:
            positions = pos_offset + jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if mode == "train":
        out = _attend(q, k, v, cfg, causal=causal)
        new_cache = None
    elif mode == "prefill":
        new_cache = _cache_write_prefill(cache, k, v)
        out = _attend(q, k, v, cfg, causal=causal)
    elif mode == "decode":
        assert S == 1
        if pages is not None:
            new_cache = _paged_cache_write(cache, k, v, pos_offset, pages)
            out = attention_paged(q, new_cache["k"], new_cache["v"], pages,
                                  kv_len=jnp.asarray(pos_offset) + 1)
            proj = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                              params["wo"])
            return proj, new_cache
        new_cache = _cache_write_decode(cache, k, v, pos_offset)
        if (cfg.fused_attention and isinstance(new_cache["k"], QTensor)
                and new_cache["k"].packed):
            # fused path: stream the packed uint32 KV words through the
            # flash-style kernel — the cache is never dequantized in HBM
            out = attention_packed(q, new_cache["k"], new_cache["v"],
                                   kv_len=pos_offset + 1)
        else:
            kc, vc = _cache_read(new_cache, cfg)
            out = _attend(q, kc, vc, cfg, causal=False,
                          kv_len=pos_offset + 1)
    else:
        raise ValueError(mode)
    proj = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd), params["wo"])
    return proj, new_cache


def _attend(q, k, v, cfg, *, causal, kv_len=None, q_offset=0):
    if cfg.opt_head_shard:
        k = _broadcast_kv(k, cfg.n_heads)
        v = _broadcast_kv(v, cfg.n_heads)
        if cfg.attn_impl == "chunked" and q.shape[1] > 1:
            return _mha_chunked(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                                q_offset=q_offset, kv_len=kv_len)
        return _mha_attention(q, k, v, causal=causal, q_offset=q_offset,
                              kv_len=kv_len)
    if cfg.attn_impl == "chunked" and q.shape[1] > 1:
        return chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                                 q_offset=q_offset, kv_len=kv_len)
    return naive_attention(q, k, v, causal=causal, q_offset=q_offset,
                           kv_len=kv_len)


# ---------------------------------------------------------------------------
# Cache plumbing
# ---------------------------------------------------------------------------
def init_cache(cfg, batch, max_seq, quantized: bool, dtype,
               fmt: F2PFormat = KV_FMT, packed: bool = False):
    K, hd = cfg.n_kv_heads, cfg.head_dim
    if quantized:
        # the code of VALUE zero (flavor-dependent: 0 for SR/SI, the top
        # payload code for LR/LI) + unit scales -> slots decode to exact 0.0
        import numpy as np

        zero_code = int(fmt.encode_nearest(np.zeros(1))[0])

        def empty():
            if packed:
                # rows never share words, so the empty cache is one packed
                # zero-code head_dim row broadcast everywhere — a token's
                # codes can never straddle a word boundary by construction
                from repro.kernels.bits import pack_bits_np

                row = pack_bits_np(
                    np.full((hd,), zero_code, np.uint32), fmt.n_bits)
                codes = jnp.broadcast_to(
                    jnp.asarray(row), (batch, max_seq, K, row.size))
            else:
                codes = jnp.full((batch, max_seq, K, hd), zero_code,
                                 jnp.dtype(fmt.code_dtype))
            return QTensor.from_parts(
                codes, jnp.ones((batch, max_seq, K, 1), jnp.float32),
                fmt, hd, (batch, max_seq, K, hd), packed=packed)

        return {"k": empty(), "v": empty()}
    return {"k": jnp.zeros((batch, max_seq, K, hd), dtype),
            "v": jnp.zeros((batch, max_seq, K, hd), dtype)}


def _rowwise_update(buf, upd, idx):
    """Per-batch-row dynamic update along the token axis: buf [B, Smax, ...],
    upd [B, S, ...], idx [B] start positions. Each row writes at its own
    offset (continuous batching: slots live at different sequence points)."""
    return jax.vmap(
        lambda b, u, i: jax.lax.dynamic_update_slice_in_dim(b, u, i, 0)
    )(buf, upd, idx)


def _qt_rowwise_update(qt: QTensor, upd: QTensor, idx):
    """:func:`_rowwise_update` over a QTensor's codes+scales coherently.
    Rows never share words in the packed layout (block = head_dim), so the
    per-row word writes are exact-relocation copies — no repacking."""
    return QTensor.from_parts(
        _rowwise_update(qt.codes, upd.codes, idx),
        _rowwise_update(qt.scales, upd.scales, idx),
        qt.fmt, qt.block, qt.shape, packed=qt.packed)


def _cache_write(cache, k, v, idx):
    if isinstance(cache["k"], QTensor):
        kf, vf = cache["k"].fmt, cache["v"].fmt
        pk = cache["k"].packed
        kq, vq = quantize_kv(k, kf, pk), quantize_kv(v, vf, pk)
        if jnp.ndim(idx):               # per-slot write positions [B]
            return {"k": _qt_rowwise_update(cache["k"], kq, idx),
                    "v": _qt_rowwise_update(cache["v"], vq, idx)}
        return {"k": cache["k"].dynamic_update(kq, idx, axis=1),
                "v": cache["v"].dynamic_update(vq, idx, axis=1)}
    if jnp.ndim(idx):
        return {"k": _rowwise_update(cache["k"], k, idx),
                "v": _rowwise_update(cache["v"], v, idx)}
    upd = jax.lax.dynamic_update_slice_in_dim
    return {"k": upd(cache["k"], k, idx, 1), "v": upd(cache["v"], v, idx, 1)}


def _paged_cache_write(cache, k, v, pos, pages):
    """Decode write straight into the pool slabs: quantize the new token's
    k/v ``[B, 1, K, hd]`` and scatter the packed words into slab page
    ``pages[b, pos // T]`` at in-page offset ``pos % T``. Rows own whole
    words (block = head_dim), so the scatter is an exact word write.
    Live slots never share a page, so the per-row scatter is conflict-free;
    retired slots all point at the engine's dump page, whose contents are
    never read (their positions are masked by kv_len)."""
    T = cache["k"].codes.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (pages.shape[0],))
    pidx = jnp.take_along_axis(jnp.asarray(pages, jnp.int32),
                               (pos // T)[:, None], axis=1)[:, 0]
    off = pos % T

    def wr(qt: QTensor, x) -> QTensor:
        up = quantize_kv(x, qt.fmt, packed=True)          # [B, 1, K, *]
        B = up.codes.shape[0]
        # slab rows hold every kv head side by side ([..., K*words])
        return QTensor.from_parts(
            qt.codes.at[pidx, off].set(up.codes.reshape(B, -1)),
            qt.scales.at[pidx, off].set(up.scales.reshape(B, -1)),
            qt.fmt, qt.block, qt.shape, packed=qt.packed)

    return {"k": wr(cache["k"], k), "v": wr(cache["v"], v)}


def _cache_write_prefill(cache, k, v):
    return _cache_write(cache, k, v, 0)


def _cache_write_decode(cache, k, v, idx):
    return _cache_write(cache, k, v, idx)


def _cache_read(cache, cfg):
    if isinstance(cache["k"], QTensor):
        dt = cfg.jnp_dtype
        return dequantize_kv(cache["k"], dt), dequantize_kv(cache["v"], dt)
    return cache["k"], cache["v"]
