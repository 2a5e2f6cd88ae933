"""Fused flash-style attention over the bit-packed F2P KV cache (DESIGN §11).

The serving decode loop used to dequantize the WHOLE quantized cache to f32
before every attention call (``models.attention._cache_read``), so the
packed-storage bandwidth win of DESIGN.md §9 died at the attention boundary.
This kernel carries the packed stream through attention: each grid step
streams one kv tile of packed uint32 words of K and V per batch row — every
kv head side by side, ``[tile, K*W]`` with ``W = packed_words(head_dim)`` —
n_bits/8 bytes per element on the KV HBM stream, decodes it branch-free
in-register (:func:`repro.kernels.f2p_quant.dequantize_tile_math`), applies
the per-(position, head) scale, and folds the tile into an online-softmax
running (acc, m, l) state. Byte-aligned codes or f32 KV are never
materialized in HBM.

Storage-order decode (``decode_order(fmt) == "planes"``): when ``n_bits``
divides 32, no field straddles a word, so with ``P = 32 // n_bits`` the
fields of a word tile split into P planes by a shift and a mask,
``(words >> p*n_bits) & mask``, and plane ``p`` holds element ``P*w + p``
of head ``h`` in lane ``h*W + w`` — the lane its word already occupies.
Nothing moves lanes to decode: the field permutation moves onto q instead
(``q_planes[p, r, h*W + w] = q[r, h, P*w + p]``, a small XLA transpose
before the kernel) and is undone on the output after it. Scores are the
elementwise plane products summed per head by a 0/1 ``[K*W, K*R]``
selection matmul; the probabilities and the per-(token, head) scales reach
their heads' lanes through the transposed 0/1 maps. Every selection matmul
runs at ``Precision.HIGHEST``, so no f32 value is rounded to bf16, and
every decoded K/V value is bitwise the value :func:`_decode_rows` gives.
Fields of other widths (6-bit) straddle words, the plane identity fails,
and those formats keep the per-head decode: ``unpack_bits_mxu`` routes one
head's bytes to its field lanes through byte-plane matmuls, head by head
(``"per_head"``).

GQA head folding: q ``[B, Sq, H, hd]`` with H = K*G is reshaped to
``[B, K, R, hd]`` rows R = G*Sq (row r = g*Sq + s), so each kv head's
decoded tile feeds all G query heads (and all Sq query positions) at once.
Causal masks recover the query position as ``q_offset + r % Sq``.

Backends (dispatch ops ``attention_packed`` / ``attention_paged``):

  ``pallas`` / ``pallas_interpret``  the Pallas kernel, grid (B, S/tile)
                                     with the kv-tile axis innermost —
                                     sequential, so every kv head's
                                     (acc, m, l) state persists in the
                                     revisited output blocks exactly like
                                     the matmul K-axis accumulator
  ``xla``                            the SAME per-tile math (shared helpers
                                     below) as a tile loop over the decoded
                                     cache under one jit — the semantics
                                     oracle

All run the identical op sequence in f32, so fused outputs are
bitwise-identical to the unpack-then-dequant-then-attend reference
(:func:`attention_packed_reference`) — pinned by ``tests/test_attention.py``
across formats × n_bits × odd sequence lengths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.f2p import F2PFormat
from repro.core.qtensor import QTensor
from repro.kernels import dispatch
from repro.kernels.bits import unpack_bits, unpack_bits_mxu
from repro.kernels.f2p_quant import dequantize_tile_math

__all__ = ["attention_packed", "attention_packed_reference",
           "attention_paged", "attention_paged_reference",
           "gather_pages_to_dense", "attention_reference", "attention_tile",
           "set_attention_tile", "autotune_attention_tile", "decode_order",
           "DEFAULT_TILE"]

# kv-tile length (cache positions per grid step). Per-(backend, n_bits)
# overrides mirror the matmul tile table (f2p_matmul._TILE_TABLE): narrow
# formats unpack more elements per word, so the sweet spot shifts with
# n_bits. Seeded by autotune_attention_tile; DEFAULT_TILE when absent.
DEFAULT_TILE = 128
_TILE_TABLE: dict[tuple[str, int], int] = {}


def attention_tile(backend: str, n_bits: int) -> int:
    """kv-tile length for (backend, n_bits) — table hit or DEFAULT_TILE."""
    return _TILE_TABLE.get((backend, int(n_bits)), DEFAULT_TILE)


def set_attention_tile(backend: str, n_bits: int, tile: int) -> None:
    _TILE_TABLE[(backend, int(n_bits))] = int(tile)


def decode_order(fmt: F2PFormat) -> str:
    """``"planes"`` where the kernels decode packed KV in storage order
    (``32 % n_bits == 0``: no field straddles a word), else ``"per_head"``
    (the per-head MXU unpack)."""
    return "planes" if 32 % fmt.n_bits == 0 else "per_head"


def _plane_counts(fmt_k: F2PFormat, fmt_v: F2PFormat, hd: int):
    """(P_k, P_v) fields per word when both caches decode in storage order
    and every head fills whole words, else None (the per-head path)."""
    if any(decode_order(f) != "planes" or hd % (32 // f.n_bits)
           for f in (fmt_k, fmt_v)):
        return None
    return 32 // fmt_k.n_bits, 32 // fmt_v.n_bits


# ---------------------------------------------------------------------------
# Shared per-tile math — ONE implementation used by the Pallas kernel body
# AND the xla tile loop, so the backends agree bitwise.
# ---------------------------------------------------------------------------
def _decode_rows(words, scales, fmt: F2PFormat, hd: int,
                 unpack=unpack_bits):
    """[..., W] uint32 words + [..., 1] f32 scales -> [..., hd] f32 values:
    unpack, branch-free decode, per-row scale. Pallas bodies pass the
    Mosaic-lowerable :func:`unpack_bits_mxu` (same integers, bit for bit)."""
    codes = unpack(words, fmt.n_bits, hd).astype(jnp.int32)
    return dequantize_tile_math(codes, fmt, jnp.float32) * scales


def _tile_mask(j, tile: int, rows: int, sq: int, causal: bool, kvlen, qoff,
               tokens_axis: int = 1):
    """Validity of kv tile ``j`` ([rows, tile], or [tile, rows] with
    ``tokens_axis=0``): position < kvlen, and (causal) position <= the
    row's query position q_offset + r % Sq."""
    shape = (rows, tile) if tokens_axis == 1 else (tile, rows)
    kpos = j * tile + jax.lax.broadcasted_iota(jnp.int32, shape, tokens_axis)
    valid = kpos < kvlen
    if causal:
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - tokens_axis)
        valid = valid & (kpos <= qoff + r % sq)
    return valid


def _online_step(q2, k_t, v_t, valid, acc, m, l, scale):
    """One online-softmax update: q2 [R,hd], k_t/v_t [T,hd] f32, valid [R,T],
    running (acc [R,hd], m [R,1], l [R,1]). Same guarded rescale as
    models.attention.chunked_attention (safe_m for fully-masked rows)."""
    s = jnp.dot(q2, k_t.T, preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    l_new = l * corr + p.sum(axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.dot(p, v_t, preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _finalize(acc, l):
    return acc / jnp.maximum(l, 1e-37)


def _fold_q(q, K: int):
    """[B, Sq, H, hd] -> [B, K, G*Sq, hd] f32 (row r = g*Sq + s)."""
    B, Sq, H, hd = q.shape
    G = H // K
    q3 = q.astype(jnp.float32).reshape(B, Sq, K, G, hd)
    return q3.transpose(0, 2, 3, 1, 4).reshape(B, K, G * Sq, hd)


def _unfold_o(o3, sq: int, dtype):
    """Inverse of :func:`_fold_q`: [B, K, G*Sq, hd] -> [B, Sq, H, hd]."""
    B, K, R, hd = o3.shape
    G = R // sq
    o = o3.reshape(B, K, G, sq, hd).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, sq, K * G, hd).astype(dtype)


# -- storage-order (plane) decode --------------------------------------------
def _select(x, sel):
    """``x @ sel`` for a 0/1 ``sel``: moves f32 lanes (one 1 per column) or
    sums lane groups on the MXU, at HIGHEST so no f32 value is rounded to
    bf16 (an ambient matmul precision cannot lower it)."""
    return jnp.dot(x, sel, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _selectors(K: int, R: int, Wk: int, Wv: int):
    """The plane layout's static 0/1 lane maps (numpy): ``sel_k [R, K*Wk,
    K*R]`` sends lane h*Wk + w to score column h*R + r; ``sel_v [R, K*R,
    K*Wv]`` sends column h*R + r to every lane of head h; ``exp_k [K,
    K*Wk]`` / ``exp_v [K, K*Wv]`` send head h's scale to its lanes."""
    col = np.arange(K * R)

    def sel(W):
        head = np.arange(K * W)[:, None] // W
        return np.stack([(head == col // R) & (col % R == r)
                         for r in range(R)]).astype(np.float32)

    def exp(W):
        return (np.arange(K)[:, None] == np.arange(K * W) // W).astype(
            np.float32)

    return sel(Wk), sel(Wv).transpose(0, 2, 1), exp(Wk), exp(Wv)


def _to_planes(x, P: int):
    """[B, N, K, hd] -> [B, P, N, K*W] storage order: plane p, lane
    h*W + w holds element P*w + p of head h (W = hd // P), the lane of the
    word that stores it (DESIGN §9's little-endian fields)."""
    B, N, K, hd = x.shape
    x = x.reshape(B, N, K, hd // P, P).transpose(0, 4, 1, 2, 3)
    return x.reshape(B, P, N, K * (hd // P))


def _from_planes(x, K: int):
    """Inverse of :func:`_to_planes`: [B, P, N, K*W] -> [B, N, K, P*W]."""
    B, P, N, L = x.shape
    x = x.reshape(B, P, N, K, L // K).transpose(0, 2, 3, 4, 1)
    return x.reshape(B, N, K, P * (L // K))


def _q_planes(q3, P: int):
    """[B, K, R, hd] folded q -> [B, P, R, K*W] in K's storage order."""
    return _to_planes(q3.transpose(0, 2, 1, 3), P)


def _o_unplanes(acc, K: int):
    """[B, P, R, K*W] output in V's storage order -> [B, K, R, hd]."""
    return _from_planes(acc, K).transpose(0, 2, 1, 3)


def _decode_planes(words, scales, fmt: F2PFormat, expand):
    """[T, K*W] uint32 words + [T, K] f32 scales -> P planes [T, K*W] f32
    in storage order (plane p = field p of every word), each value bitwise
    the value :func:`_decode_rows` gives it. ``expand`` is the [K, K*W] 0/1
    map of each head's scale to its lanes (exact)."""
    n = fmt.n_bits
    sc = _select(scales, expand)
    mask = jnp.uint32((1 << n) - 1)
    return [dequantize_tile_math(((words >> jnp.uint32(p * n)) & mask)
                                 .astype(jnp.int32), fmt, jnp.float32) * sc
            for p in range(32 // n)]


def _tile_sum(x):
    """Sum over axis 0 as a tree of halves: one association on every
    backend. (XLA's CPU reductions pick their order from the operand's
    layout, which differs between the kernel body and the xla loop.)"""
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        y = x[:h] + x[h:2 * h]
        x = jnp.concatenate([y, x[2 * h:]]) if x.shape[0] % 2 else y
    return x


def _plane_step(q, k, v, valid, acc, m, l, scale, sel_k, sel_v):
    """One online-softmax update of every kv head at once, in storage order.

    q ``[Pk][R]`` rows [1, K*Wk] (q in K's planes); k ``[Pk]`` / v ``[Pv]``
    decoded planes [T, K*W]; valid [T, K*R]; running acc ``[Pv][R]`` rows
    [1, K*Wv], m / l [1, K*R] (column h*R + r); sel_k / sel_v the
    ``[R]`` 0/1 maps of :func:`_selectors`. The math is :func:`_online_step`
    per (head, row), with the sums over head_dim and tile reassociated."""
    s = None
    for r, sel in enumerate(sel_k):
        prod = k[0] * q[0][r]
        for kp, qp in zip(k[1:], q[1:]):
            prod = prod + kp * qp[r]
        sr = _select(prod, sel)            # head sums, row r's columns only
        s = sr if s is None else s + sr
    s = jnp.where(valid, s * scale, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    l_new = l * corr + _tile_sum(p)
    acc_new = [list(a) for a in acc]
    for r, sel in enumerate(sel_v):
        pe, ce = _select(p, sel), _select(corr, sel)
        for i, vp in enumerate(v):
            acc_new[i][r] = acc[i][r] * ce + _tile_sum(pe * vp)
    return acc_new, m_new, l_new


def _plane_finalize(acc, l, sel_v):
    """[Pv][R] rows of acc / their (head, row)'s l, spread to its lanes."""
    return [[_finalize(a, _select(l, sel)) for a, sel in zip(rows, sel_v)]
            for rows in acc]


# ---------------------------------------------------------------------------
# xla backend: decode + online-softmax attention under ONE jit — the
# semantics oracle the Pallas kernel is pinned against. The staged reference
# runs the same tile loop on a cache dequantized by a separate jit.
# ---------------------------------------------------------------------------
def _attend_decoded(q3, k, v, lens, *, sq, causal, tile, planes):
    """Tile loop over decoded ``[B, S, K, hd]`` f32 k/v: per head
    (``planes`` None) or in storage order (``planes`` = (P_k, P_v))."""
    B, K, R, hd = q3.shape
    S = k.shape[1]
    nt = -(-S // tile)
    pad = nt * tile - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kvlen, qoff = lens[:, 0], lens[:, 1]          # per-batch [B]
    scale = 1.0 / math.sqrt(hd)
    if planes is not None:
        return _planes_loop(q3, k, v, kvlen, qoff, planes=planes, sq=sq,
                            causal=causal, tile=tile, scale=scale)
    # [nt, B, K, tile, hd]: per-(batch, head) tiles in kernel layout
    kt = k.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    step = jax.vmap(jax.vmap(_online_step, in_axes=(0, 0, 0, None, 0, 0, 0,
                                                    None)),
                    in_axes=(0, 0, 0, 0, 0, 0, 0, None))

    def body(carry, inp):
        acc, m, l = carry
        j, (kb, vb) = inp
        valid = jax.vmap(
            lambda kl, qo: _tile_mask(j, tile, R, sq, causal, kl, qo)
        )(kvlen, qoff)                            # [B, R, tile]
        return step(q3, kb, vb, valid, acc, m, l, scale), None

    acc0 = jnp.zeros((B, K, R, hd), jnp.float32)
    m0 = jnp.full((B, K, R, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, R, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (jnp.arange(nt), (kt, vt)))
    return _finalize(acc, l)


def _planes_loop(q3, k, v, kvlen, qoff, *, planes, sq, causal, tile, scale):
    """Storage-order twin of the plane kernel: one batch row at a time
    (``lax.map``) and one tile at a time (``lax.scan``), so every
    :func:`_plane_step` call sees the kernel body's shapes."""
    B, K, R, hd = q3.shape
    Pk, Pv = planes
    nt = k.shape[1] // tile
    sel_k, sel_v, _, _ = (jnp.asarray(x) for x in
                          _selectors(K, R, hd // Pk, hd // Pv))
    sel_k, sel_v = list(sel_k), list(sel_v)

    def tiles(x, P):                    # [B, S, K, hd] -> [B, nt, P, tile, L]
        x = _to_planes(x, P)
        return x.reshape(B, P, nt, tile, -1).transpose(0, 2, 1, 3, 4)

    def row(args):
        qp, kt, vt, kl, qo = args
        q = [[qp[p, r:r + 1] for r in range(R)] for p in range(Pk)]

        def body(carry, inp):
            acc, m, l = carry
            j, kb, vb = inp
            valid = _tile_mask(j, tile, K * R, sq, causal, kl, qo,
                               tokens_axis=0)
            return _plane_step(q, list(kb), list(vb), valid, acc, m, l,
                               scale, sel_k, sel_v), None

        acc0 = [[jnp.zeros((1, K * hd // Pv), jnp.float32)] * R] * Pv
        m0 = jnp.full((1, K * R), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((1, K * R), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                      (jnp.arange(nt), kt, vt))
        return jnp.stack([jnp.concatenate(rows, axis=0)
                          for rows in _plane_finalize(acc, l, sel_v)])

    acc = jax.lax.map(row, (_q_planes(q3, Pk), tiles(k, Pk), tiles(v, Pv),
                            kvlen, qoff))
    return _o_unplanes(acc, K)


@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq",
                                             "causal", "tile"))
def _attention_xla(q3, kw, ks, vw, vs, lens, *, fmt_k, fmt_v, sq, causal,
                   tile):
    hd = q3.shape[-1]
    return _attend_decoded(q3, _decode_rows(kw, ks, fmt_k, hd),
                           _decode_rows(vw, vs, fmt_v, hd), lens, sq=sq,
                           causal=causal, tile=tile,
                           planes=_plane_counts(fmt_k, fmt_v, hd))


_reference_jit = jax.jit(_attend_decoded,
                         static_argnames=("sq", "causal", "tile", "planes"))


# ---------------------------------------------------------------------------
# Pallas kernels: grid (B, S/tile), kv-tile axis innermost/sequential; the
# online-softmax state of every kv head lives in the revisited per-batch
# output blocks (same persistence contract the packed matmul uses for its
# K-axis accumulator). One grid step covers all K heads of its kv tile, fed
# as lane-dense rows — words [tile, K*W] and scales [tile, K], every kv
# head side by side — because Mosaic wants the last two block dims to be
# whole array dims or (8, 128) multiples, and [.., K, W] blocks are 1 head
# tall. Lengths ride in SMEM as scalar-prefetch operands. The dense and the
# paged kernel differ only in how the word tile arrives (one block, or
# one block per page), so both run one body per decode order.
# ---------------------------------------------------------------------------
def _init_state(o_ref, m_ref, l_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)


def _attend_planes(fmt_k, fmt_v, sq, causal, scale, tile, nt, kw, ks, vw, vs,
                   q_ref, selk_ref, selv_ref, expk_ref, expv_ref,
                   o_ref, m_ref, l_ref, kvlen, qoff):
    """Fold kv tile ``program_id(1)`` into every head's running (acc, m, l)
    in storage order: ``q_ref`` [Pk, R, K*Wk] (q in K's planes), ``o_ref``
    [Pv, R, K*Wv], ``m_ref`` / ``l_ref`` [1, K*R]. The math is the xla
    loop's :func:`_plane_step`, op for op."""
    j = pl.program_id(1)
    _init_state(o_ref, m_ref, l_ref)
    Pk, R = q_ref.shape[0], q_ref.shape[1]
    Pv = o_ref.shape[0]
    sel_k = [selk_ref[r] for r in range(R)]
    sel_v = [selv_ref[r] for r in range(R)]
    k = _decode_planes(kw, ks, fmt_k, expk_ref[...])
    v = _decode_planes(vw, vs, fmt_v, expv_ref[...])
    valid = _tile_mask(j, tile, m_ref.shape[-1], sq, causal, kvlen, qoff,
                       tokens_axis=0)
    q = [[q_ref[p, r:r + 1, :] for r in range(R)] for p in range(Pk)]
    acc = [[o_ref[i, r:r + 1, :] for r in range(R)] for i in range(Pv)]
    acc, m, l = _plane_step(q, k, v, valid, acc, m_ref[...], l_ref[...],
                            scale, sel_k, sel_v)
    for i in range(Pv):
        for r in range(R):
            o_ref[i, r:r + 1, :] = acc[i][r]
    m_ref[...] = m
    l_ref[...] = l

    @pl.when(j == nt - 1)
    def _fin():
        for i, rows in enumerate(_plane_finalize(acc, l, sel_v)):
            for r, o in enumerate(rows):
                o_ref[i, r:r + 1, :] = o


def _attend_heads(fmt_k, fmt_v, sq, causal, scale, tile, nt, kw, ks, vw, vs,
                  q_ref, o_ref, m_ref, l_ref, kvlen, qoff):
    """Per-head twin for formats whose fields straddle words: ``q_ref`` /
    ``o_ref`` [K, R, hd], ``m_ref`` / ``l_ref`` [K, R, 1]. Head h's words
    start at lane h*W, which the MXU unpack absorbs; its scale column is a
    masked lane sum (one value plus zeros: exact). The per-head math is the
    xla scan's, op for op."""
    j = pl.program_id(1)
    _init_state(o_ref, m_ref, l_ref)
    K, R, hd = q_ref.shape
    Wk, Wv = kw.shape[-1] // K, vw.shape[-1] // K
    lane = jax.lax.broadcasted_iota(jnp.int32, ks.shape, 1)
    valid = _tile_mask(j, tile, R, sq, causal, kvlen, qoff)

    def head(words, scales, fmt, W, h):
        col = jnp.sum(jnp.where(lane == h, scales, 0.0), axis=1,
                      keepdims=True)
        return _decode_rows(
            words, col, fmt, hd,
            lambda w, n, c: unpack_bits_mxu(w, n, c, offset=h * W))

    for h in range(K):
        acc, m, l = _online_step(q_ref[h], head(kw, ks, fmt_k, Wk, h),
                                 head(vw, vs, fmt_v, Wv, h), valid,
                                 o_ref[h], m_ref[h], l_ref[h], scale)
        o_ref[h] = acc
        m_ref[h] = m
        l_ref[h] = l

    @pl.when(j == nt - 1)
    def _fin():
        for h in range(K):
            o_ref[h] = _finalize(o_ref[h], l_ref[h])


def _kernel(attend, n_prefetch, ppt, *refs):
    """Pallas body shared by the dense and the paged call: after the
    scalar-prefetch refs (the paged call's page table, then lengths) come
    q, ``ppt`` word blocks each of K and V (pages concatenate back into the
    contiguous tile), the two scale blocks, then ``attend``'s constant maps
    and state outputs."""
    len_ref = refs[n_prefetch - 1]
    q_ref, rest = refs[n_prefetch], refs[n_prefetch + 1:]
    kw, vw = (jnp.concatenate([r[...] for r in rest[i * ppt:(i + 1) * ppt]],
                              axis=0) if ppt > 1 else rest[i * ppt][...]
              for i in range(2))
    ks_ref, vs_ref = rest[2 * ppt:2 * ppt + 2]
    b = pl.program_id(0)
    attend(kw, ks_ref[...], vw, vs_ref[...], q_ref, *rest[2 * ppt + 2:],
           len_ref[b, 0], len_ref[b, 1])


def _pallas_attend(q3, kv_specs, kv_args, prefetch, nt, ppt, *, fmt_k, fmt_v,
                   sq, causal, tile, interpret):
    """The pallas_call of both kernels: ``kv_specs``/``kv_args`` are the K
    and V word blocks and the [B, S, K] scale rows; ``prefetch`` the scalar
    operands (lengths last). Picks the body by decode order."""
    B, K, R, hd = q3.shape
    scale = 1.0 / math.sqrt(hd)   # static: python float, f32 at use sites
    statics = (fmt_k, fmt_v, sq, causal, scale, tile, nt)

    def whole(x):                       # the same block at every grid step
        return pl.BlockSpec(x.shape, lambda *a, _n=x.ndim: (0,) * _n)

    def per_row(shape):                 # batch row b's block
        return pl.BlockSpec((None,) + shape,
                            lambda b, *a, _n=len(shape): (b,) + (0,) * _n)

    planes = _plane_counts(fmt_k, fmt_v, hd)
    if planes is None:
        attend = functools.partial(_attend_heads, *statics)
        q, consts = q3, []
        state = [(K, R, hd), (K, R, 1), (K, R, 1)]
    else:
        (Pk, Pv), (Lk, Lv) = planes, (kv_args[0].shape[-1],
                                      kv_args[ppt].shape[-1])
        attend = functools.partial(_attend_planes, *statics)
        q = _q_planes(q3, Pk)
        consts = [jnp.asarray(x) for x in
                  _selectors(K, R, Lk // K, Lv // K)]
        state = [(Pv, R, Lv), (1, K * R), (1, K * R)]
    out = pl.pallas_call(
        functools.partial(_kernel, attend, len(prefetch), ppt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B, nt),
            in_specs=[per_row(q.shape[1:])] + kv_specs
            + [whole(x) for x in consts],
            out_specs=[per_row(s) for s in state]),
        out_shape=[jax.ShapeDtypeStruct((B,) + s, jnp.float32)
                   for s in state],
        interpret=interpret,
    )(*prefetch, q, *kv_args, *consts)[0]
    return out if planes is None else _o_unplanes(out, K)


def _heads_in_lanes(x):
    """[..., K, W] -> [..., K*W]: every kv head of a position in one row."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq", "causal",
                                             "tile", "interpret"))
def _attention_pallas(q3, kw, ks, vw, vs, lens, *, fmt_k, fmt_v, sq, causal,
                      tile, interpret):
    S = kw.shape[1]
    nt = -(-S // tile)
    pad = nt * tile - S
    if pad:
        # zero words decode to the format's code-0 value, but every padded
        # position sits at kpos >= S >= kvlen and is masked to exp(-inf)=0
        kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv = [_heads_in_lanes(x) for x in (kw, vw, ks, vs)]
    specs = [pl.BlockSpec((None, tile, x.shape[-1]),
                          lambda b, j, lens: (b, j, 0)) for x in kv]
    return _pallas_attend(q3, specs, kv, (lens,), nt, 1, fmt_k=fmt_k,
                          fmt_v=fmt_v, sq=sq, causal=causal, tile=tile,
                          interpret=interpret)


# ---------------------------------------------------------------------------
# Registry wiring + the public QTensor-consuming entry points
# ---------------------------------------------------------------------------
@dispatch.register("attention_packed", dispatch.PALLAS)
def _attn_pallas(q3, kw, ks, vw, vs, lens, **kw_static):
    return _attention_pallas(q3, kw, ks, vw, vs, lens, interpret=False,
                             **kw_static)


@dispatch.register("attention_packed", dispatch.PALLAS_INTERPRET)
def _attn_pallas_interp(q3, kw, ks, vw, vs, lens, **kw_static):
    return _attention_pallas(q3, kw, ks, vw, vs, lens, interpret=True,
                             **kw_static)


@dispatch.register("attention_packed", dispatch.XLA)
def _attn_xla(q3, kw, ks, vw, vs, lens, **kw_static):
    return _attention_xla(q3, kw, ks, vw, vs, lens, **kw_static)


def _check_cache(qt: QTensor, hd: int, what: str) -> None:
    if not isinstance(qt, QTensor):
        raise TypeError(f"{what} must be a QTensor, got {type(qt).__name__}")
    if not qt.packed:
        raise ValueError(f"{what} must be bit-packed (QTensor.packed=True); "
                         "unpacked caches take the _cache_read path")
    if qt.block != hd or qt.shape[-1] != hd:
        raise ValueError(f"{what} must be blocked over head_dim={hd}, got "
                         f"block={qt.block} shape={qt.shape}")


def _make_lens(kv_len, q_offset, B: int, S: int):
    """Per-batch ``[B, 2]`` int32 (kv_len, q_offset). Scalars broadcast to
    every batch row; ``[B]`` vectors thread per-slot lengths (the
    continuous-batching engine's ragged decode)."""
    kv_len = jnp.asarray(S if kv_len is None else kv_len, jnp.int32)
    kv_len = jnp.minimum(kv_len, S)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    return jnp.stack([jnp.broadcast_to(kv_len, (B,)),
                      jnp.broadcast_to(q_offset, (B,))], axis=1)


def attention_packed(q, kq: QTensor, vq: QTensor, *, kv_len=None,
                     causal: bool = False, q_offset=0,
                     backend: str | None = None, tile: int | None = None):
    """Fused attention straight off the packed KV cache.

    q ``[B, Sq, H, hd]`` (any float dtype; math runs in f32), kq/vq packed
    QTensors of logical shape ``[B, S, K, hd]`` with block = hd (the
    canonical cache layout of ``models.attention.init_cache``). ``kv_len``
    masks cache positions >= kv_len (decode: pos + 1); ``causal`` adds the
    in-window causal mask using ``q_offset`` as the first query position.
    Both accept a scalar or a per-batch ``[B]`` vector (per-slot lengths in
    the continuous-batching engine). Returns ``[B, Sq, H, hd]`` in q's dtype.
    """
    B, Sq, H, hd = q.shape
    _check_cache(kq, hd, "kq")
    _check_cache(vq, hd, "vq")
    S, K = kq.shape[1], kq.shape[2]
    if H % K:
        raise ValueError(f"n_heads {H} not a multiple of kv heads {K}")
    b, fn = dispatch.lookup("attention_packed", backend)
    if tile is None:
        tile = attention_tile(b, kq.fmt.n_bits)
    tile = max(1, min(int(tile), S))
    lens = _make_lens(kv_len, q_offset, B, S)
    o3 = fn(_fold_q(q, K), kq.codes, kq.scales, vq.codes, vq.scales, lens,
            fmt_k=kq.fmt, fmt_v=vq.fmt, sq=Sq, causal=bool(causal), tile=tile)
    return _unfold_o(o3, Sq, q.dtype)


# ---------------------------------------------------------------------------
# Paged variant: the KV never leaves the pool. Instead of a dense per-request
# cache row [B, S, K, hd], each batch row carries an ordered page-id list into
# the pool slabs [P, page_tokens, K*words] (``serve.paging.PagedKVPool``, the
# leading layer-group axis stripped by the model's scan). Every kv tile
# gathers its packed uint32 words and per-row scales THROUGH the page table —
# word-granular by construction, since §9's block=head_dim packing gives every
# token whole words and a page boundary can never split one. Tiles must span
# whole pages (tile % page_tokens == 0), which the default tile table
# satisfies for power-of-two page sizes; with the same tile, outputs are
# bitwise-identical to gathering the pages into a dense row and running
# :func:`attention_packed` (decode is elementwise per token row, so
# decode(gather) == gather(decode) exactly, and the online-softmax tile loop
# sees identical values in identical order).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq",
                                             "causal", "tile"))
def _attention_paged_xla(q3, kw, ks, vw, vs, pages, lens, *, fmt_k, fmt_v,
                         sq, causal, tile):
    """Gather the table's page rows into a dense cache row (a pure word
    copy), then run the dense xla tile loop: one scan body for both the
    paged and the dense twin, so they agree bit for bit."""
    K = q3.shape[1]

    def dense(slab):
        x = jnp.take(slab, pages, axis=0)              # [B, n, T, K*W|K]
        return x.reshape(x.shape[0], x.shape[1] * x.shape[2], K, -1)

    return _attention_xla(q3, dense(kw), dense(ks), dense(vw), dense(vs),
                          lens, fmt_k=fmt_k, fmt_v=fmt_v, sq=sq,
                          causal=causal, tile=tile)


@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq", "causal",
                                             "tile", "interpret"))
def _attention_paged_pallas(q3, kw, ks, vw, vs, pages, lens, *, fmt_k, fmt_v,
                            sq, causal, tile, interpret):
    """The kernel body's word tile arrives as ``ppt`` separate page blocks,
    DMA'd straight from the pool slabs through the scalar-prefetched page
    table; concatenating them re-forms the contiguous tile, after which the
    math is byte-for-byte the dense kernel's."""
    B, K = q3.shape[:2]
    T = kw.shape[1]
    ppt = tile // T
    nt = pages.shape[1] // ppt

    def page_spec(x, p):
        # one page block per spec: page p of kv tile j lives at slab page
        # ids[b, j*ppt + p] — the indirection happens in the index_map, so
        # the kernel never sees a dense row and each page is one DMA
        return pl.BlockSpec(
            (None, T, x.shape[-1]),
            lambda b, j, ids, lens, _p=p: (ids[b, j * ppt + _p], 0, 0))

    # the per-token scales ([P, T, K] f32, K lanes wide) are gathered by
    # XLA into a dense [B, S, K] row: a paged [T, K] block would make XLA
    # relayout the whole scales slab into the kernel's tiled layout (K=8
    # lanes padded to 128) on every call
    def dense(slab):
        return jnp.take(slab, pages, axis=0).reshape(B, nt * tile, K)

    specs = [page_spec(x, p) for x in (kw, vw) for p in range(ppt)]
    specs += [pl.BlockSpec((None, tile, K),
                           lambda b, j, ids, lens: (b, j, 0))] * 2
    args = [kw] * ppt + [vw] * ppt + [dense(ks), dense(vs)]
    return _pallas_attend(q3, specs, args, (pages, lens), nt, ppt,
                          fmt_k=fmt_k, fmt_v=fmt_v, sq=sq, causal=causal,
                          tile=tile, interpret=interpret)


@dispatch.register("attention_paged", dispatch.PALLAS)
def _attn_paged_pallas(q3, kw, ks, vw, vs, pages, lens, **kw_static):
    return _attention_paged_pallas(q3, kw, ks, vw, vs, pages, lens,
                                   interpret=False, **kw_static)


@dispatch.register("attention_paged", dispatch.PALLAS_INTERPRET)
def _attn_paged_pallas_interp(q3, kw, ks, vw, vs, pages, lens, **kw_static):
    return _attention_paged_pallas(q3, kw, ks, vw, vs, pages, lens,
                                   interpret=True, **kw_static)


@dispatch.register("attention_paged", dispatch.XLA)
def _attn_paged_xla(q3, kw, ks, vw, vs, pages, lens, **kw_static):
    return _attention_paged_xla(q3, kw, ks, vw, vs, pages, lens, **kw_static)


def _check_slab(qt: QTensor, hd: int, what: str) -> None:
    """A packed pool slab of logical ``[P, T, K*hd]`` blocked over hd (the
    ``PagedKVPool`` layout: codes ``[P, T, K*W]``, scales ``[P, T, K]``)."""
    if not isinstance(qt, QTensor):
        raise TypeError(f"{what} must be a QTensor, got {type(qt).__name__}")
    if not qt.packed:
        raise ValueError(f"{what} must be bit-packed (QTensor.packed=True)")
    if qt.block != hd:
        raise ValueError(f"{what} must be blocked over head_dim={hd}, got "
                         f"block={qt.block} shape={qt.shape}")
    if qt.codes.ndim != 3 or qt.shape[-1] % hd:
        raise ValueError(f"{what} slab codes must be [n_pages, page_tokens, "
                         f"K*words], got {qt.codes.shape}")


def attention_paged(q, kq: QTensor, vq: QTensor, pages, *, kv_len=None,
                    causal: bool = False, q_offset=0,
                    backend: str | None = None, tile: int | None = None):
    """Fused attention THROUGH a page table — no dense KV row exists.

    q ``[B, Sq, H, hd]``; kq/vq are packed pool-slab QTensors of logical
    shape ``[n_pages, page_tokens, K*hd]`` (a ``serve.paging.PagedKVPool``
    slab with the layer-group axis stripped by the model scan); ``pages``
    ``[B, max_pages]`` int32 orders each batch row's pages. The logical
    per-row sequence length is ``max_pages * page_tokens``;
    ``kv_len``/``q_offset`` behave exactly as in :func:`attention_packed`
    (positions >= kv_len — including every position of unassigned/garbage
    page ids — contribute exactly 0.0, because the mask sets their scores to
    -inf before exp). With the same ``tile``, output is bitwise-identical to
    :func:`attention_packed` over :func:`gather_pages_to_dense` of the same
    table.
    """
    B, Sq, H, hd = q.shape
    _check_slab(kq, hd, "kq")
    _check_slab(vq, hd, "vq")
    P, T = kq.codes.shape[0], kq.codes.shape[1]
    K = kq.shape[-1] // hd
    if H % K:
        raise ValueError(f"n_heads {H} not a multiple of kv heads {K}")
    pages = jnp.asarray(pages, jnp.int32)
    if pages.ndim != 2 or pages.shape[0] != B:
        raise ValueError(f"pages must be [B={B}, max_pages], "
                         f"got {pages.shape}")
    maxp = pages.shape[1]
    S = maxp * T
    b, fn = dispatch.lookup("attention_paged", backend)
    if tile is None:
        tile = attention_tile(b, kq.fmt.n_bits)
    tile = max(1, min(int(tile), S))
    if tile % T:
        raise ValueError(
            f"kv tile {tile} not a multiple of page_tokens {T}: paged tiles "
            "must span whole pages (pick a page size dividing the attention "
            "tile so the paged and copy-in engines share a tile)")
    ppt = tile // T
    nt = -(-maxp // ppt)
    # clamp garbage ids defensively (masked anyway) and pad the table out to
    # whole tiles; padding pages sit at positions >= S >= kv_len -> masked
    pages = jnp.clip(pages, 0, P - 1)
    if nt * ppt > maxp:
        pages = jnp.pad(pages, ((0, 0), (0, nt * ppt - maxp)))
    lens = _make_lens(kv_len, q_offset, B, S)
    o3 = fn(_fold_q(q, K), kq.codes, kq.scales, vq.codes, vq.scales, pages,
            lens, fmt_k=kq.fmt, fmt_v=vq.fmt, sq=Sq, causal=bool(causal),
            tile=tile)
    return _unfold_o(o3, Sq, q.dtype)


def gather_pages_to_dense(qt: QTensor, pages) -> QTensor:
    """Materialize page tables as a dense cache: slab ``[P, T, K*hd]`` +
    ``pages [B, maxp]`` -> ``[B, maxp*T, K, hd]`` QTensor. A pure uint32
    word/scale gather — zero repack, bit-exact by construction. The copy-in
    comparator for :func:`attention_paged` (and what
    ``PagedKVPool.load_into_slot`` does for the copy-in engine)."""
    hd = qt.block
    _check_slab(qt, hd, "slab")
    K = qt.shape[-1] // hd
    pages = jnp.asarray(pages, jnp.int32)
    codes = jnp.take(qt.codes, pages, axis=0)     # [B, maxp, T, K*W]
    scales = jnp.take(qt.scales, pages, axis=0)
    B, mp, T = codes.shape[:3]
    return QTensor.from_parts(codes.reshape(B, mp * T, K, -1),
                              scales.reshape(B, mp * T, K, 1), qt.fmt, hd,
                              (B, mp * T, K, hd), packed=qt.packed)


def attention_paged_reference(q, kq: QTensor, vq: QTensor, pages, *,
                              kv_len=None, causal: bool = False, q_offset=0,
                              tile: int | None = None):
    """The copy-in path the paged kernel replaces: gather the page table
    into a dense row (HBM copy), then run :func:`attention_packed` on it.
    The bitwise-parity oracle for :func:`attention_paged`."""
    kd = gather_pages_to_dense(kq, pages)
    vd = gather_pages_to_dense(vq, pages)
    if tile is None:
        b, _ = dispatch.lookup("attention_paged", None)
        tile = attention_tile(b, kq.fmt.n_bits)
    return attention_packed(q, kd, vd, kv_len=kv_len, causal=causal,
                            q_offset=q_offset, backend="xla", tile=tile)


def attention_reference(q, k, v, *, kv_len=None, causal: bool = False,
                        q_offset=0, tile: int = DEFAULT_TILE, planes=None):
    """Dense-KV online-softmax reference: the SAME tile loop as the fused
    backends, on already-dequantized ``[B, S, K, hd]`` k/v. Matches
    ``naive_attention`` numerically and the fused paths bitwise (given the
    same tile and, for formats decoded in storage order, their ``planes``
    = (P_k, P_v) fields per word)."""
    B, Sq, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    tile = max(1, min(int(tile), S))
    lens = _make_lens(kv_len, q_offset, B, S)
    o3 = _reference_jit(_fold_q(q, K), k.astype(jnp.float32),
                        v.astype(jnp.float32), lens, sq=Sq,
                        causal=bool(causal), tile=tile, planes=planes)
    return _unfold_o(o3, Sq, q.dtype)


def attention_packed_reference(q, kq: QTensor, vq: QTensor, *, kv_len=None,
                               causal: bool = False, q_offset=0,
                               tile: int = DEFAULT_TILE):
    """The unfused serving path the kernel replaces, staged as SEPARATE jits:
    dequantize the whole cache to f32 in HBM (unpack + decode via
    ``QTensor.dequantize``), then attend. The bitwise-parity oracle for
    :func:`attention_packed` — and the honest wall-clock comparator in
    ``benchmarks.run --only attention``."""
    k = kq.dequantize(jnp.float32)
    v = vq.dequantize(jnp.float32)
    return attention_reference(
        q, k, v, kv_len=kv_len, causal=causal, q_offset=q_offset, tile=tile,
        planes=_plane_counts(kq.fmt, vq.fmt, q.shape[-1]))


def autotune_attention_tile(backend: str, n_bits: int, *,
                            candidates=(64, 128, 256, 512),
                            shape=(2, 2048, 4, 128), reps: int = 3,
                            fmt: F2PFormat | None = None) -> int:
    """Time :func:`attention_packed` over candidate kv-tile lengths on a
    decode-shaped problem and install the winner in the tile table. Returns
    the winning tile. Mirrors ``f2p_matmul.autotune_matmul_tiles``."""
    import time

    import numpy as np

    from repro.core import qtensor as QT
    from repro.core.f2p import Flavor

    if fmt is None:
        fmt = F2PFormat(n_bits, 2, Flavor.SR, signed=True)
    B, S, K, hd = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, 1, 2 * K, hd)).astype(np.float32))
    kd = jnp.asarray(rng.normal(size=(B, S, K, hd)).astype(np.float32))
    vd = jnp.asarray(rng.normal(size=(B, S, K, hd)).astype(np.float32))
    kq = QT.quantize(kd, fmt, block=hd, packed=True, backend="xla")
    vq = QT.quantize(vd, fmt, block=hd, packed=True, backend="xla")
    best, best_t = None, DEFAULT_TILE
    for t in candidates:
        if t > S:
            continue

        def run():
            return attention_packed(q, kq, vq, kv_len=S - 1, backend=backend,
                                    tile=t)

        run().block_until_ready()  # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(max(1, reps)):
            run().block_until_ready()
        dt = time.perf_counter() - t0
        if best is None or dt < best:
            best, best_t = dt, t
    set_attention_tile(backend, n_bits, best_t)
    return best_t
