"""Shared uint32 bit primitives: murmur3 finalizer + n-bit field packing.

jnp + numpy twins throughout — the device/host implementations must stay
bit-identical, so there is exactly one copy of each algorithm per backend.

``fmix32`` (murmur3 finalizer) is the avalanche mix used by the sketch row
hashes (``repro.sketch.hashing``) and the counter-advance uniform stream
(``repro.kernels.f2p_counter``): the constants are load-bearing
(DESIGN.md §6.2).

``pack_bits`` / ``unpack_bits`` are the packed-storage primitives
(DESIGN.md §9): dense little-endian packing of ``n_bits``-wide code fields
into uint32 words along the LAST axis. Element ``i`` of a row occupies bits
``[i*n_bits, (i+1)*n_bits)`` of that row's bit stream; stream bit ``b``
lives at bit ``b % 32`` of word ``b // 32``; within a field the LSB comes
first. Rows never share words — each last-axis row packs into its own
``packed_words(n, n_bits)`` words (trailing slack bits are zero), so
leading-axis slicing / dynamic_update / all_gather of packed buffers stay
word-aligned for free.

``n_bits`` is static (a Python int): jit specializes per width, and the
pure-reshape/shift formulation below contains no gathers (DESIGN.md §3).
Mosaic cannot lower its lane-splitting reshapes, so Pallas TPU kernel
bodies use the bit-identical ``pack_bits_mxu`` / ``unpack_bits_mxu``
twins, which route lanes through the MXU.

``packed_nbytes`` is the ONE canonical packed-size formula — FL wire
accounting, ``autotune.policy._leaf_bits`` and the checkpoint shrink check
all call it (two hand-rolled copies of this already drifted once; see
ISSUE 5).
"""
from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fmix32", "fmix32_np", "packed_words", "packed_nbytes",
           "pack_bits", "unpack_bits", "pack_bits_np", "unpack_bits_np",
           "pack_bits_mxu", "unpack_bits_mxu"]


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer: full-avalanche mix of a uint32 lane (jnp)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def fmix32_np(x: np.ndarray) -> np.ndarray:
    """Bit-identical numpy twin of :func:`fmix32` (host aggregation path)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    return x


# ---------------------------------------------------------------------------
# Packed n-bit fields (DESIGN.md §9)
# ---------------------------------------------------------------------------
def packed_words(n_elems: int, n_bits: int) -> int:
    """uint32 words holding ``n_elems`` dense little-endian n-bit fields."""
    return -(-(int(n_elems) * int(n_bits)) // 32)


def packed_nbytes(n_elems: int, n_bits: int) -> int:
    """Bytes of one packed row — the canonical packed-size formula (wire
    accounting, ``_leaf_bits(bits_mode='packed')`` and the checkpoint
    shrink check must all agree, so they all call this)."""
    return 4 * packed_words(n_elems, n_bits)


def _check_n_bits(n_bits: int) -> int:
    n_bits = int(n_bits)
    if not 1 <= n_bits <= 32:
        raise ValueError(f"n_bits must be in [1, 32], got {n_bits}")
    return n_bits


def _superblock(n_bits: int) -> tuple[int, int]:
    """(elements, words) of the smallest group whose packed layout repeats:
    L = lcm(32, n_bits) / n_bits elements fill exactly L*n_bits/32 words."""
    L = 32 // math.gcd(32, n_bits)
    return L, L * n_bits // 32


def _mask32(n_bits: int):
    return (1 << n_bits) - 1 if n_bits < 32 else 0xFFFFFFFF


def pack_bits(codes: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Pack ``[..., n]`` unsigned codes (< 2^n_bits) into ``[..., W]`` uint32
    words, little-endian dense along the last axis (W = packed_words(n)).

    Static ``n_bits``: the loop below unrolls over ONE superblock (the
    lcm(32, n_bits)-bit repeat period — at most 32 elements), so the traced
    program is a handful of static-shift/OR lanes per word regardless of
    ``n``. No gathers, no bit-matrix blowup — it fuses under jit (Pallas
    kernel bodies use :func:`pack_bits_mxu`)."""
    n_bits = _check_n_bits(n_bits)
    c = codes.astype(jnp.uint32) & jnp.uint32(_mask32(n_bits))
    n = c.shape[-1]
    lead = c.shape[:-1]
    W = packed_words(n, n_bits)
    L, WL = _superblock(n_bits)
    nsb = -(-n // L)
    pad = nsb * L - n
    if pad:
        c = jnp.pad(c, [(0, 0)] * (c.ndim - 1) + [(0, pad)])
    cs = c.reshape(*lead, nsb, L)
    terms: list[list] = [[] for _ in range(WL)]
    for i in range(L):
        o = i * n_bits
        w0, s = o >> 5, o & 31
        ci = cs[..., i]
        terms[w0].append((ci << jnp.uint32(s)) if s else ci)
        if s + n_bits > 32:  # field straddles into the next word
            terms[w0 + 1].append(ci >> jnp.uint32(32 - s))
    words = jnp.stack([functools.reduce(operator.or_, t) for t in terms],
                      axis=-1)
    return words.reshape(*lead, nsb * WL)[..., :W]


def unpack_bits(words: jnp.ndarray, n_bits: int, count: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: ``[..., W]`` uint32 words -> ``[...,
    count]`` uint32 codes. Static ``n_bits``/``count``; gather-free (same
    unrolled-superblock formulation as :func:`pack_bits`)."""
    n_bits = _check_n_bits(n_bits)
    count = int(count)
    w = words.astype(jnp.uint32)
    lead = w.shape[:-1]
    W = w.shape[-1]
    if W < packed_words(count, n_bits):
        raise ValueError(
            f"{W} words cannot hold {count} fields of {n_bits} bits")
    mask = jnp.uint32(_mask32(n_bits))
    L, WL = _superblock(n_bits)
    nsb = -(-count // L)
    need = nsb * WL
    if need > W:
        w = jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, need - W)])
    elif need < W:  # caller handed a longer row; the tail is other fields
        w = w[..., :need]
    ws = w.reshape(*lead, nsb, WL)
    elems = []
    for i in range(L):
        o = i * n_bits
        w0, s = o >> 5, o & 31
        lo = (ws[..., w0] >> jnp.uint32(s)) if s else ws[..., w0]
        if s + n_bits > 32:
            lo = lo | (ws[..., w0 + 1] << jnp.uint32(32 - s))
        elems.append(lo & mask)
    out = jnp.stack(elems, axis=-1)
    return out.reshape(*lead, nsb * L)[..., :count]


def pack_bits_np(codes: np.ndarray, n_bits: int) -> np.ndarray:
    """Bit-identical numpy twin of :func:`pack_bits` (host/wire paths)."""
    n_bits = _check_n_bits(n_bits)
    # mask exactly like the jnp twin: an out-of-range code must not bleed
    # into its neighbor's field on one backend but not the other
    c = np.asarray(codes).astype(np.uint32) & np.uint32(_mask32(n_bits))
    n = c.shape[-1]
    lead = c.shape[:-1]
    W = packed_words(n, n_bits)
    if 32 % n_bits == 0:
        per = 32 // n_bits
        pad = W * per - n
        if pad:
            c = np.pad(c, [(0, 0)] * (c.ndim - 1) + [(0, pad)])
        cw = c.reshape(*lead, W, per)
        shifts = (np.arange(per, dtype=np.uint32) * np.uint32(n_bits))
        return np.bitwise_or.reduce(cw << shifts, axis=-1).astype(np.uint32)
    bits = (c[..., None] >> np.arange(n_bits, dtype=np.uint32)) & np.uint32(1)
    flat = bits.reshape(*lead, n * n_bits)
    pad = W * 32 - n * n_bits
    if pad:
        flat = np.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    w = flat.reshape(*lead, W, 32)
    return np.bitwise_or.reduce(
        w << np.arange(32, dtype=np.uint32), axis=-1).astype(np.uint32)


def unpack_bits_np(words: np.ndarray, n_bits: int, count: int) -> np.ndarray:
    """Bit-identical numpy twin of :func:`unpack_bits`."""
    n_bits = _check_n_bits(n_bits)
    count = int(count)
    w = np.asarray(words).astype(np.uint32)
    lead = w.shape[:-1]
    W = w.shape[-1]
    if W < packed_words(count, n_bits):
        raise ValueError(
            f"{W} words cannot hold {count} fields of {n_bits} bits")
    mask = np.uint32((1 << n_bits) - 1) if n_bits < 32 \
        else np.uint32(0xFFFFFFFF)
    if 32 % n_bits == 0:
        per = 32 // n_bits
        shifts = (np.arange(per, dtype=np.uint32) * np.uint32(n_bits))
        c = (w[..., None] >> shifts) & mask
        return c.reshape(*lead, W * per)[..., :count]
    bits = (w[..., None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    flat = bits.reshape(*lead, W * 32)[..., :count * n_bits]
    b = flat.reshape(*lead, count, n_bits)
    acc = np.zeros(b.shape[:-1], np.uint32)
    for j in range(n_bits):
        acc |= b[..., j] << np.uint32(j)
    return acc


# ---------------------------------------------------------------------------
# Kernel-body twins. Mosaic (the TPU Pallas compiler) cannot lower the
# lane-splitting reshapes above for most widths: interleaving fields across
# lanes is a lane permutation, and the VPU has no lane gather. These twins
# move lanes through the MXU instead: a word row is split into four byte
# planes, and each byte a field needs is routed to the field's lane by a
# 0/1 selection matmul. Bytes are < 256, so bf16 holds them exactly and the
# f32 accumulator sums one byte plus zeros (or disjoint bit fields) exactly:
# the results are the same integers as pack_bits/unpack_bits, bit for bit.
# Inputs are 2D ([rows, lanes]) and n_bits <= 16 (the kernel code widths).
# ---------------------------------------------------------------------------
# bf16 x bf16 products of bytes and 0/1 are exact in one MXU pass; pinned so
# an ambient jax.default_matmul_precision("highest") cannot request an f32
# contraction of bf16 operands, which Mosaic rejects
_EXACT = jax.lax.Precision.DEFAULT


def _field_bytes(n_bits: int, count: int):
    """Per field lane i: first stream byte (i*n_bits >> 3), in-byte shift,
    and the most bytes any field touches (static)."""
    start = np.arange(count) * n_bits
    span = int(((start & 7) + n_bits + 7).max() // 8) if count else 1
    return start >> 3, start & 7, span


def _plane_used(byte_minus_plane: np.ndarray, W: int) -> bool:
    """Static skip: does any field's stream byte land in byte plane j of a
    word that exists (byte index - j a multiple of 4 within the row)?"""
    d = byte_minus_plane
    return bool(np.any((d % 4 == 0) & (d >= 0) & (d // 4 < W)))


def _kernel_width(n_bits: int) -> int:
    n_bits = _check_n_bits(n_bits)
    if n_bits > 16:
        raise ValueError(f"kernel pack/unpack supports n_bits <= 16, "
                         f"got {n_bits}")
    return n_bits


def unpack_bits_mxu(words: jnp.ndarray, n_bits: int, count: int,
                    offset: int = 0):
    """:func:`unpack_bits` for Pallas TPU kernel bodies: ``[R, W]`` uint32
    words -> ``[R, count]`` uint32 codes of the packed row that starts at
    word ``offset`` (a lane offset the selection matmul absorbs for free),
    through byte-plane selection matmuls (see the section comment)."""
    n_bits, count = _kernel_width(n_bits), int(count)
    w = words.astype(jnp.uint32)
    W = w.shape[-1]
    if W - offset < packed_words(count, n_bits):
        raise ValueError(
            f"{W - offset} words cannot hold {count} fields of {n_bits} bits")
    first, _, span = _field_bytes(n_bits, count)
    first = first + 4 * offset
    wi = jax.lax.broadcasted_iota(jnp.int32, (W, count), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (W, count), 1)
    lane_first = ((lane * n_bits) >> 3) + 4 * offset
    planes = [((w >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)).astype(
        jnp.int32).astype(jnp.float32).astype(jnp.bfloat16) for j in range(4)]
    acc = None
    for m in range(span):
        g = None
        for j in range(4):
            if not _plane_used(first + m - j, W):
                continue
            sel = (4 * wi + j == lane_first + m).astype(jnp.bfloat16)
            d = jnp.dot(planes[j], sel, precision=_EXACT,
                        preferred_element_type=jnp.float32)
            g = d if g is None else g + d
        if g is None:
            continue
        b = g.astype(jnp.int32) << (8 * m)
        acc = b if acc is None else acc | b
    shift = (jax.lax.broadcasted_iota(jnp.int32, (1, count), 1)
             * n_bits) & 7
    return ((acc >> shift) & ((1 << n_bits) - 1)).astype(jnp.uint32)


def pack_bits_mxu(codes: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """:func:`pack_bits` for Pallas TPU kernel bodies: ``[R, n]`` codes ->
    ``[R, packed_words(n)]`` uint32 words. Each field, shifted to its
    in-byte offset, contributes up to three byte pieces; a selection matmul
    sums the pieces of every stream byte into its byte plane (the pieces
    are disjoint bit fields, so the sum is their OR)."""
    n_bits = _kernel_width(n_bits)
    n = codes.shape[-1]
    W = packed_words(n, n_bits)
    first, _, span = _field_bytes(n_bits, n)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    c = codes.astype(jnp.int32) & ((1 << n_bits) - 1)
    v = c << ((lane * n_bits) & 7)
    fi = (jax.lax.broadcasted_iota(jnp.int32, (n, W), 0) * n_bits) >> 3
    ww = jax.lax.broadcasted_iota(jnp.int32, (n, W), 1)
    planes: list = [None] * 4
    for m in range(span):
        piece = ((v >> (8 * m)) & 0xFF).astype(jnp.float32).astype(
            jnp.bfloat16)
        for j in range(4):
            if not _plane_used(first + m - j, W):
                continue
            sel = (fi + m == 4 * ww + j).astype(jnp.bfloat16)
            d = jnp.dot(piece, sel, precision=_EXACT,
                        preferred_element_type=jnp.float32)
            planes[j] = d if planes[j] is None else planes[j] + d
    out = None
    for j, p in enumerate(planes):
        if p is None:
            continue
        b = p.astype(jnp.int32).astype(jnp.uint32) << jnp.uint32(8 * j)
        out = b if out is None else out | b
    return out


@functools.partial(jax.jit, static_argnames=("n_bits",))
def pack_bits_jit(codes, n_bits: int):
    """Jitted eager entry point (host callers outside a surrounding jit)."""
    return pack_bits(codes, n_bits)


@functools.partial(jax.jit, static_argnames=("n_bits", "count"))
def unpack_bits_jit(words, n_bits: int, count: int):
    """Jitted eager entry point (host callers outside a surrounding jit)."""
    return unpack_bits(words, n_bits, count)
