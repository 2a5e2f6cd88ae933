"""Fused flash-style attention over the bit-packed F2P KV cache (DESIGN §11).

The serving decode loop used to dequantize the WHOLE quantized cache to f32
before every attention call (``models.attention._cache_read``), so the
packed-storage bandwidth win of DESIGN.md §9 died at the attention boundary.
This kernel carries the packed stream through attention: each grid step
streams one (tile, packed_words(head_dim)) uint32 WORD tile of K and V per
(batch, kv-head) from the cache layout ``[B, S, K, W]`` — n_bits/8 bytes per
element on the KV HBM stream — unpacks it with the gather-free superblock
lanes of :func:`repro.kernels.bits.unpack_bits`, decodes branch-free
in-register (:func:`repro.kernels.f2p_quant.dequantize_tile_math`), applies
the per-(position, head) scale, and folds the tile into an online-softmax
running (acc, m, l) state. Byte-aligned codes or f32 KV are never
materialized in HBM.

GQA head folding: q ``[B, Sq, H, hd]`` with H = K*G is reshaped to
``[B, K, R, hd]`` rows R = G*Sq (row r = g*Sq + s), so each kv head's
decoded tile feeds all G query heads (and all Sq query positions) at once;
one kernel step streams the tile of every kv head of one batch row. Causal
masks recover the query position as ``q_offset + r % Sq``.

Backends (dispatch op ``attention_packed``):

  ``pallas`` / ``pallas_interpret``  the Pallas kernel, grid (B, S/tile)
                                     with the kv-tile axis innermost —
                                     sequential, so every kv head's
                                     (acc, m, l) state persists in the
                                     revisited output blocks exactly like
                                     the matmul K-axis accumulator
  ``xla``                            the SAME per-tile math (shared helpers
                                     below) as a ``lax.scan`` over kv tiles,
                                     with unpack + decode + attention fused
                                     under one jit — the semantics oracle

All three run the identical op sequence in f32, so fused outputs are
bitwise-identical to the unpack-then-dequant-then-attend reference
(:func:`attention_packed_reference`) — pinned by ``tests/test_attention.py``
across formats × n_bits × odd sequence lengths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
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
           "set_attention_tile", "autotune_attention_tile", "DEFAULT_TILE"]

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


# ---------------------------------------------------------------------------
# Shared per-tile math — ONE implementation used by the Pallas kernel body
# AND the xla scan, so the backends agree bitwise.
# ---------------------------------------------------------------------------
def _decode_rows(words, scales, fmt: F2PFormat, hd: int,
                 unpack=unpack_bits):
    """[..., W] uint32 words + [..., 1] f32 scales -> [..., hd] f32 values:
    unpack, branch-free decode, per-row scale. Pallas bodies pass the
    Mosaic-lowerable :func:`unpack_bits_mxu` (same integers, bit for bit)."""
    codes = unpack(words, fmt.n_bits, hd).astype(jnp.int32)
    return dequantize_tile_math(codes, fmt, jnp.float32) * scales


def _tile_mask(j, tile: int, rows: int, sq: int, causal: bool, kvlen, qoff):
    """[rows, tile] validity of kv tile ``j``: position < kvlen, and (causal)
    position <= the row's query position q_offset + r % Sq."""
    kpos = j * tile + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
    valid = kpos < kvlen
    if causal:
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
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


# ---------------------------------------------------------------------------
# xla backend: unpack + decode + online-softmax attention under ONE jit —
# the semantics oracle the Pallas kernel is pinned against.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq",
                                             "causal", "tile"))
def _attention_xla(q3, kw, ks, vw, vs, lens, *, fmt_k, fmt_v, sq, causal,
                   tile):
    B, K, R, hd = q3.shape
    S = kw.shape[1]
    k = _decode_rows(kw, ks, fmt_k, hd)          # [B, S, K, hd] f32
    v = _decode_rows(vw, vs, fmt_v, hd)
    nt = -(-S // tile)
    pad = nt * tile - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # [nt, B, K, tile, hd]: per-(batch, head) tiles in kernel layout
    kt = k.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    kvlen, qoff = lens[:, 0], lens[:, 1]          # per-batch [B]
    scale = 1.0 / math.sqrt(hd)
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


# ---------------------------------------------------------------------------
# Pallas kernels: grid (B, S/tile), kv-tile axis innermost/sequential; the
# online-softmax state of every kv head lives in the revisited per-batch
# output blocks (same persistence contract the packed matmul uses for its
# K-axis accumulator). One grid step covers all K heads of its kv tile, fed
# as lane-dense rows — words [tile, K*W] and scales [tile, K], every kv
# head side by side — because Mosaic wants the last two block dims to be
# whole array dims or (8, 128) multiples, and [.., K, W] blocks are 1 head
# tall. Head h's words start at lane h*W, which the MXU unpack absorbs; its
# scale column is a masked lane sum (one value plus zeros: exact). Lengths
# ride in SMEM as scalar-prefetch operands.
# ---------------------------------------------------------------------------
def _attend_heads(fmt_k, fmt_v, sq, causal, scale, tile, nt, kw, ks, vw, vs,
                  q_ref, o_ref, m_ref, l_ref, kvlen, qoff):
    """Fold kv tile ``program_id(1)`` (rows ``kw``/``vw`` [tile, K*W],
    ``ks``/``vs`` [tile, K]) into every head's running (acc, m, l). The
    per-head math is the xla scan's, op for op."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

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


def _fused_kernel(fmt_k, fmt_v, sq, causal, scale, tile, nt,
                  len_ref, q_ref, kw_ref, ks_ref, vw_ref, vs_ref,
                  o_ref, m_ref, l_ref):
    b = pl.program_id(0)
    _attend_heads(fmt_k, fmt_v, sq, causal, scale, tile, nt, kw_ref[...],
                  ks_ref[...], vw_ref[...], vs_ref[...], q_ref, o_ref,
                  m_ref, l_ref, len_ref[b, 0], len_ref[b, 1])


def _kernel_out(B: int, K: int, R: int, hd: int, index_map):
    """Out specs + shapes of the per-batch (acc, m, l) state blocks."""
    specs = [pl.BlockSpec((None, K, R, d), index_map) for d in (hd, 1, 1)]
    shapes = [jax.ShapeDtypeStruct((B, K, R, d), jnp.float32)
              for d in (hd, 1, 1)]
    return specs, shapes


def _heads_in_lanes(x):
    """[..., K, W] -> [..., K*W]: every kv head of a position in one row."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq", "causal",
                                             "tile", "interpret"))
def _attention_pallas(q3, kw, ks, vw, vs, lens, *, fmt_k, fmt_v, sq, causal,
                      tile, interpret):
    B, K, R, hd = q3.shape
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
    scale = 1.0 / math.sqrt(hd)   # static: python float, f32 at use sites
    kv = [_heads_in_lanes(x) for x in (kw, ks, vw, vs)]
    out_specs, out_shape = _kernel_out(B, K, R, hd,
                                       lambda b, j, lens: (b, 0, 0, 0))
    out, _, _ = pl.pallas_call(
        functools.partial(_fused_kernel, fmt_k, fmt_v, sq, causal, scale,
                          tile, nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nt),
            in_specs=[pl.BlockSpec((None, K, R, hd),
                                   lambda b, j, lens: (b, 0, 0, 0))]
            + [pl.BlockSpec((None, tile, x.shape[-1]),
                            lambda b, j, lens: (b, j, 0)) for x in kv],
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(lens, q3, *kv)
    return out


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


def _paged_kernel(fmt_k, fmt_v, sq, causal, scale, tile, nt, ppt,
                  ids_ref, len_ref, q_ref, *refs):
    """Pallas body: the grid's kv step j receives its word tile as ``ppt``
    separate page blocks, DMA'd straight from the pool slabs through the
    scalar-prefetched page table (the index_maps below read ``ids_ref``).
    Concatenating the page blocks re-forms the contiguous tile, after which
    the math is byte-for-byte the dense kernel's."""
    kw, vw = (
        jnp.concatenate([r[...] for r in refs[i * ppt:(i + 1) * ppt]],
                        axis=0)
        for i in range(2))
    ks_ref, vs_ref, o_ref, m_ref, l_ref = refs[2 * ppt:]
    ks, vs = ks_ref[...], vs_ref[...]
    b = pl.program_id(0)
    _attend_heads(fmt_k, fmt_v, sq, causal, scale, tile, nt, kw, ks, vw, vs,
                  q_ref, o_ref, m_ref, l_ref, len_ref[b, 0], len_ref[b, 1])


@functools.partial(jax.jit, static_argnames=("fmt_k", "fmt_v", "sq", "causal",
                                             "tile", "interpret"))
def _attention_paged_pallas(q3, kw, ks, vw, vs, pages, lens, *, fmt_k, fmt_v,
                            sq, causal, tile, interpret):
    B, K, R, hd = q3.shape
    T = kw.shape[1]
    ppt = tile // T
    nt = pages.shape[1] // ppt
    scale = 1.0 / math.sqrt(hd)

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

    in_specs = [pl.BlockSpec((None, K, R, hd),
                             lambda b, j, ids, lens: (b, 0, 0, 0))]
    for x in (kw, vw):
        in_specs.extend(page_spec(x, p) for p in range(ppt))
    in_specs += [pl.BlockSpec((None, tile, K),
                              lambda b, j, ids, lens: (b, j, 0))] * 2
    out_specs, out_shape = _kernel_out(
        B, K, R, hd, lambda b, j, ids, lens: (b, 0, 0, 0))
    out, _, _ = pl.pallas_call(
        functools.partial(_paged_kernel, fmt_k, fmt_v, sq, causal, scale,
                          tile, nt, ppt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nt), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(pages, lens, q3, *([kw] * ppt), *([vw] * ppt), dense(ks), dense(vs))
    return out


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
                        q_offset=0, tile: int = DEFAULT_TILE):
    """Dense-KV online-softmax reference: the SAME tile loop as the fused
    backends, on already-dequantized ``[B, S, K, hd]`` k/v. Matches
    ``naive_attention`` numerically and the fused paths bitwise (given the
    same tile)."""
    B, Sq, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    tile = max(1, min(int(tile), S))
    lens = _make_lens(kv_len, q_offset, B, S)
    o3 = _reference_jit(_fold_q(q, K), k.astype(jnp.float32),
                        v.astype(jnp.float32), lens, sq=Sq,
                        causal=bool(causal), tile=tile)
    return _unfold_o(o3, Sq, q.dtype)


@functools.partial(jax.jit, static_argnames=("sq", "causal", "tile"))
def _reference_jit(q3, k, v, lens, *, sq, causal, tile):
    B, K, R, hd = q3.shape
    S = k.shape[1]
    nt = -(-S // tile)
    pad = nt * tile - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kt = k.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nt, tile, K, hd).transpose(1, 0, 3, 2, 4)
    kvlen, qoff = lens[:, 0], lens[:, 1]          # per-batch [B]
    scale = 1.0 / math.sqrt(hd)
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
    return attention_reference(q, k, v, kv_len=kv_len, causal=causal,
                               q_offset=q_offset, tile=tile)


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
