"""Pallas TPU kernel: fused F2P8-dequant matmul  y = x @ dequant(W).

Serving path for F2P8-quantized weights: W lives in HBM as uint8 codes +
per-block f32 scales (1.03 B/param). Each grid step streams an (K_T, N_T)
code tile into VMEM (1 byte/elem — half the bf16 footprint, so double the
effective HBM bandwidth on the weight stream), dequantizes in-register with
the branch-free decode (no LUT/gather — DESIGN.md §3), and feeds the MXU
tile. Accumulation in f32 across the K grid axis.

Tiling: grid (M/M_T, N/N_T, K/K_T); x tile (M_T,K_T) bf16/f32, codes tile
(K_T,N_T) uint8, out (M_T,N_T) f32 — MXU-aligned multiples of 128 on every
matmul dim. The scales block is the whole (K/block, N_T) column strip (a
(K_T/block, N_T) block is too short for Mosaic's 8-sublane rule); each K
step slices its K_T/block rows out of it.

Oracle: ref_dequant_matmul (pure jnp) — tests sweep shapes/dtypes/formats
and assert allclose within f32 matmul tolerance.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.f2p import F2PFormat, Flavor
from repro.core.qtensor import block_scales
from repro.kernels import dispatch
from repro.kernels.bits import (pack_bits, packed_words, unpack_bits,
                                unpack_bits_mxu)
from repro.kernels.f2p_quant import dequantize_tile_math, quantize_tile_math

WEIGHT_FMT = F2PFormat(n_bits=8, h_bits=2, flavor=Flavor.SR, signed=True)

M_T, N_T, K_T = 128, 256, 256

# Per-(backend, n_bits) (M_T, N_T, K_T) overrides for the PACKED kernel —
# the same tile treatment as f2p_attention._TILE_TABLE: narrower formats
# pack more elements per word tile, so the VMEM/compute balance shifts with
# n_bits. Seeded by autotune_matmul_tiles (benchmarks or operators); the
# module defaults above apply when a key is absent. Constraints per entry:
# K % K_T == 0 and K_T % block == 0 at call time, N_T % 32 == 0 (column
# tiles must land on word boundaries for every n_bits).
_TILE_TABLE: dict[tuple[str, int], tuple[int, int, int]] = {}


def matmul_tiles(backend: str, n_bits: int) -> tuple[int, int, int]:
    """(M_T, N_T, K_T) for the packed kernel on (backend, n_bits)."""
    return _TILE_TABLE.get((backend, int(n_bits)), (M_T, N_T, K_T))


def set_matmul_tiles(backend: str, n_bits: int,
                     tiles: tuple[int, int, int]) -> None:
    mt, nt, kt = (int(t) for t in tiles)
    if nt % 32:
        raise ValueError(f"N_T {nt} not word-aligned (multiple of 32)")
    _TILE_TABLE[(backend, int(n_bits))] = (mt, nt, kt)


def quantize_weight(w, fmt: F2PFormat = WEIGHT_FMT, block: int = 128,
                    packed: bool = False):
    """w [K,N] -> (codes uint8 [K,N], scales f32 [K/block, N]). The scale
    block runs along K (the contraction axis) so dequant*x accumulates per
    K-block — matching the kernel's K-tiled loop.

    ``packed=True`` packs each K-row's N codes into little-endian uint32
    words -> (words uint32 [K, packed_words(N, n_bits)], scales): the
    storage layout ``f2p_dequant_matmul_packed`` streams (n_bits/8 bytes
    per weight on the HBM weight stream instead of the code dtype's 1-2)."""
    K, N = w.shape
    assert K % block == 0
    wb = w.astype(jnp.float32).reshape(K // block, block, N)
    # scales via the one canonical implementation (core.qtensor), which
    # blocks the LAST axis — feed it the [N, K/block, block] view
    scale = block_scales(jnp.moveaxis(wb, -1, 0), fmt).T
    codes = quantize_tile_math((wb / scale[:, None, :]).astype(jnp.float32),
                               fmt).reshape(K, N)
    if packed:
        return pack_bits(codes, fmt.n_bits), scale
    return codes, scale


def ref_dequant_matmul(x, codes, scales, fmt: F2PFormat = WEIGHT_FMT,
                       block: int = 128):
    """Oracle: dequantize the whole W then a plain f32 matmul."""
    K, N = codes.shape
    w = dequantize_tile_math(codes, fmt, jnp.float32)
    w = (w.reshape(K // block, block, N) * scales[:, None, :]).reshape(K, N)
    return jnp.dot(x.astype(jnp.float32), w)


def _scale_tile(w, s_ref, block: int):
    """w [K_T, N_T] times this K step's scales, one block of rows at a time.
    Each scale row is picked out of the whole-strip block by a masked sum
    (one value plus zeros: exact) — Mosaic cannot slice a dynamic row
    offset that is not a multiple of 8."""
    kt = w.shape[0]
    nkb = kt // block
    strip = s_ref[...]                              # [K/block, N_T]
    rows = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 0)
    k0 = pl.program_id(2) * nkb
    parts = [w[r * block:(r + 1) * block]
             * jnp.sum(jnp.where(rows == k0 + r, strip, 0.0), axis=0,
                       keepdims=True)
             for r in range(nkb)]
    return parts[0] if nkb == 1 else jnp.concatenate(parts, axis=0)


def _kernel(fmt, block, nk, x_ref, c_ref, s_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)              # [M_T, K_T]
    w = dequantize_tile_math(c_ref[...], fmt, jnp.float32)  # [K_T, N_T]
    w = _scale_tile(w, s_ref, block)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def f2p_dequant_matmul(x, codes, scales, *, fmt: F2PFormat = WEIGHT_FMT,
                       block: int = 128, interpret: bool | None = None):
    """y = x @ dequant(codes, scales); x [M,K], codes [K,N] uint8.

    ``interpret=None`` resolves via the dispatch registry: compiled on TPU,
    interpreter elsewhere."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    return _dequant_matmul_jit(x, codes, scales, fmt=fmt, block=block,
                               interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def _dequant_matmul_jit(x, codes, scales, *, fmt: F2PFormat,
                        block: int, interpret: bool):
    M, K = x.shape
    K2, N = codes.shape
    assert K == K2 and K % K_T == 0 and K_T % block == 0
    mt, nt = min(M_T, M), min(N_T, N)
    assert M % mt == 0 and N % nt == 0
    grid = (M // mt, N // nt, K // K_T)
    return pl.pallas_call(
        functools.partial(_kernel, fmt, block, K // K_T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, K_T), lambda i, j, k: (i, k)),
            pl.BlockSpec((K_T, nt), lambda i, j, k: (k, j)),
            pl.BlockSpec((K // block, nt), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((mt, nt), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, codes, scales)


# ---------------------------------------------------------------------------
# Packed-weight variant (DESIGN.md §9): W lives in HBM as dense n-bit fields
# in uint32 words — each grid step streams an (K_T, words(N_T)) WORD tile
# into VMEM (n_bits/8 bytes per weight: 0.75 B at 6-bit vs the 1 B uint8
# stream, 2.7x less than bf16) and unpacks in-register immediately before
# the branch-free decode. Word alignment: the column tile is widened until
# its word tile is a multiple of 128 lanes (_packed_col_tile), which also
# makes it a multiple of 32 codes, so every column tile covers an integral
# number of words for any n_bits; rows (the K axis) never share words, so K
# tiling is unaffected.
# ---------------------------------------------------------------------------
def _packed_col_tile(N: int, nt0: int, n_bits: int) -> int:
    """Column tile >= ``nt0`` whose codes AND packed words are multiples of
    128 lanes (nt * n_bits % 4096 == 0), or all N columns when no such tile
    divides N."""
    q = math.lcm(128, 4096 // math.gcd(4096, n_bits))
    nt = -(-nt0 // q) * q
    return nt if nt < N and N % nt == 0 else N
def _packed_kernel(fmt, block, nk, x_ref, w_ref, s_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)              # [M_T, K_T]
    nt = s_ref.shape[-1]
    codes = unpack_bits_mxu(w_ref[...], fmt.n_bits, nt).astype(jnp.int32)
    w = dequantize_tile_math(codes, fmt, jnp.float32)       # [K_T, N_T]
    w = _scale_tile(w, s_ref, block)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def f2p_dequant_matmul_packed(x, words, scales, *,
                              fmt: F2PFormat = WEIGHT_FMT, block: int = 128,
                              interpret: bool | None = None,
                              tiles: tuple[int, int, int] | None = None):
    """y = x @ dequant(unpack(words), scales); words [K, packed_words(N)]
    uint32 from ``quantize_weight(..., packed=True)``. ``tiles=None``
    resolves (M_T, N_T, K_T) from the per-(backend, n_bits) tile table."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    if tiles is None:
        b = dispatch.PALLAS_INTERPRET if interpret else dispatch.PALLAS
        tiles = matmul_tiles(b, fmt.n_bits)
    return _dequant_matmul_packed_jit(x, words, scales, fmt=fmt, block=block,
                                      interpret=bool(interpret),
                                      tiles=tuple(int(t) for t in tiles))


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block", "interpret", "tiles"))
def _dequant_matmul_packed_jit(x, words, scales, *, fmt: F2PFormat,
                               block: int, interpret: bool,
                               tiles: tuple[int, int, int]):
    mt0, nt0, kt0 = tiles
    M, K = x.shape
    N = scales.shape[-1]
    K2, W = words.shape
    assert K == K2 and K % kt0 == 0 and kt0 % block == 0
    assert W == packed_words(N, fmt.n_bits), (W, N, fmt.n_bits)
    mt, nt = min(mt0, M), _packed_col_tile(N, nt0, fmt.n_bits)
    assert M % mt == 0
    wt = packed_words(nt, fmt.n_bits)
    grid = (M // mt, N // nt, K // kt0)
    return pl.pallas_call(
        functools.partial(_packed_kernel, fmt, block, K // kt0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, kt0), lambda i, j, k: (i, k)),
            pl.BlockSpec((kt0, wt), lambda i, j, k: (k, j)),
            pl.BlockSpec((K // block, nt), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((mt, nt), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, words, scales)


# ---------------------------------------------------------------------------
# Registry wiring: serve paths pick the backend through one dispatch point
# ---------------------------------------------------------------------------
@dispatch.register("dequant_matmul", dispatch.PALLAS)
def _matmul_pallas(x, codes, scales, *, fmt=WEIGHT_FMT, block=128):
    return f2p_dequant_matmul(x, codes, scales, fmt=fmt, block=block,
                              interpret=False)


@dispatch.register("dequant_matmul", dispatch.PALLAS_INTERPRET)
def _matmul_pallas_interp(x, codes, scales, *, fmt=WEIGHT_FMT, block=128):
    return f2p_dequant_matmul(x, codes, scales, fmt=fmt, block=block,
                              interpret=True)


@dispatch.register("dequant_matmul", dispatch.XLA)
@functools.partial(jax.jit, static_argnames=("fmt", "block"))
def _matmul_xla(x, codes, scales, *, fmt=WEIGHT_FMT, block=128):
    return ref_dequant_matmul(x, codes, scales, fmt, block)


@dispatch.register("dequant_matmul_packed", dispatch.PALLAS)
def _matmul_packed_pallas(x, words, scales, *, fmt=WEIGHT_FMT, block=128):
    return f2p_dequant_matmul_packed(x, words, scales, fmt=fmt, block=block,
                                     interpret=False)


@dispatch.register("dequant_matmul_packed", dispatch.PALLAS_INTERPRET)
def _matmul_packed_pallas_interp(x, words, scales, *, fmt=WEIGHT_FMT,
                                 block=128):
    return f2p_dequant_matmul_packed(x, words, scales, fmt=fmt, block=block,
                                     interpret=True)


@dispatch.register("dequant_matmul_packed", dispatch.XLA)
@functools.partial(jax.jit, static_argnames=("fmt", "block"))
def _matmul_packed_xla(x, words, scales, *, fmt=WEIGHT_FMT, block=128):
    N = scales.shape[-1]
    codes = unpack_bits(words, fmt.n_bits, N).astype(jnp.int32)
    return ref_dequant_matmul(x, codes, scales, fmt, block)


def autotune_matmul_tiles(backend: str, n_bits: int, *,
                          candidates=((128, 256, 256), (128, 128, 256),
                                      (64, 256, 128), (128, 256, 128)),
                          shape=(256, 1024, 1024), reps: int = 3,
                          fmt: F2PFormat | None = None, block: int = 128
                          ) -> tuple[int, int, int]:
    """Time the packed kernel over candidate (M_T, N_T, K_T) tiles on a
    serve-shaped matmul and install the winner in the tile table (the same
    treatment as ``f2p_attention.autotune_attention_tile``). ``backend``
    must be a pallas variant — the xla path has no tiles. Candidates that
    do not divide the probe shape or violate word/block alignment are
    skipped. Returns the winning tiles."""
    import time

    import numpy as np

    if backend not in (dispatch.PALLAS, dispatch.PALLAS_INTERPRET):
        raise ValueError(f"tile autotune is for pallas variants, not "
                         f"{backend!r}")
    interpret = backend == dispatch.PALLAS_INTERPRET
    if fmt is None:
        fmt = F2PFormat(n_bits, 2, Flavor.SR, signed=True)
    M, K, N = shape
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    words, scales = quantize_weight(w, fmt, block=block, packed=True)
    best, best_t = None, (M_T, N_T, K_T)
    for t in candidates:
        mt, nt, kt = t
        if K % kt or kt % block or nt % 32 or M % min(mt, M) \
                or N % min(nt, N):
            continue

        def run():
            return f2p_dequant_matmul_packed(x, words, scales, fmt=fmt,
                                             block=block, interpret=interpret,
                                             tiles=t)

        run().block_until_ready()  # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(max(1, reps)):
            run().block_until_ready()
        dt = time.perf_counter() - t0
        if best is None or dt < best:
            best, best_t = dt, t
    set_matmul_tiles(backend, n_bits, best_t)
    return best_t


def dequant_matmul(x, codes, scales, *, fmt: F2PFormat = WEIGHT_FMT,
                   block: int = 128, backend: str | None = None,
                   packed: bool = False):
    """Backend-dispatched y = x @ dequant(codes, scales). With
    ``packed=True``, ``codes`` is the uint32 word stream of
    ``quantize_weight(..., packed=True)`` and the unpack fuses into the
    kernel (Pallas) / the surrounding HLO (XLA)."""
    op = "dequant_matmul_packed" if packed else "dequant_matmul"
    _, fn = dispatch.lookup(op, backend)
    return fn(x, codes, scales, fmt=fmt, block=block)
