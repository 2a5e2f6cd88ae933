"""Pallas TPU kernels: block-scaled F2P quantize / dequantize.

TPU adaptation (see DESIGN.md §3): no lookup tables — encode/decode are
branch-free VPU lane arithmetic:

  encode:  exact floor(log2 x) via f32 bitcast -> exponent-bucket V ->
           per-bucket mantissa width (integer ops) -> round-half-up mantissa
           (exact in f32: all intermediates fit 24-bit significands) ->
           field assembly with variable shifts.
  decode:  field split with variable shifts -> ldexp (exact).

Tiling: elementwise over (rows, cols); BlockSpec tiles of TILE_R rows by
a column tile (:func:`_col_tile`) that is the whole row, or 128 scale blocks
when a row holds a multiple of more: Mosaic wants the last block dim of
every operand — codes, packed words AND the per-block scales — to be a
multiple of 128 lanes or the whole array dim.

Supported: h_bits in {1,2}, n_bits in [6,16] — the paper's operating points.
Exactness: encode of a given f32 value is bit-exact vs repro.kernels.ref
(ties half-up == oracle's ties-to-larger-magnitude); the only shared rounding
is the f32 division by the scale, identical in both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.f2p import F2PFormat
from repro.core.qtensor import block_scales
from repro.kernels import dispatch
from repro.kernels.bits import (pack_bits, pack_bits_mxu, packed_words,
                                unpack_bits, unpack_bits_mxu)

__all__ = ["quantize_tile_math", "dequantize_tile_math", "dequantize_lut",
           "f2p_quantize_pallas", "f2p_dequantize_pallas",
           "f2p_quantize_xla", "f2p_dequantize_xla",
           "f2p_quantize_packed_pallas", "f2p_dequantize_packed_pallas",
           "f2p_quantize_packed_xla", "f2p_dequantize_packed_xla"]

# Row tile: 8 sublanes.
TILE_R = 8


def _exp2i(n: jnp.ndarray) -> jnp.ndarray:
    """Exact 2^n for int32 n in [-126, 127], built by bit assembly (no libm)."""
    return jax.lax.bitcast_convert_type(((n + 127) << 23).astype(jnp.int32),
                                        jnp.float32)


def _fmt_consts(fmt: F2PFormat):
    if fmt.h_bits not in (1, 2):
        raise ValueError("kernel supports h_bits in {1,2}")
    if fmt.n_bits > 16:
        raise ValueError(
            f"kernel tile math stores codes as uint16 — n_bits={fmt.n_bits} "
            "would truncate silently; wider formats (the paper's 19-bit "
            "point) go through the host encode path (core.f2p)")
    nu, h = fmt.payload_bits, fmt.h_bits
    sgn = fmt.flavor.exponent_sign
    return nu, h, sgn, fmt.vmax, fmt.v_sub, fmt.v_top, fmt.bias


def quantize_tile_math(x: jnp.ndarray, fmt: F2PFormat) -> jnp.ndarray:
    """Branch-free exact nearest-F2P encode of f32 magnitudes+signs -> codes.

    Pure jnp on purpose: runs identically inside the Pallas kernel body and
    under plain jit (the `ops.py` fallback path when Pallas is unavailable)."""
    nu, h, sgn, vmax, v_sub, v_top, bias = _fmt_consts(fmt)
    x = x.astype(jnp.float32)
    sign = jnp.signbit(x) if fmt.signed else jnp.zeros(x.shape, bool)
    mag = jnp.abs(x)

    # exact floor(log2 mag) via bitcast; f32-subnormal/zero inputs -> bucket 0
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32)
    bexp = (bits >> 23) & 0xFF
    k = bexp - 127
    is_zero = bexp == 0

    v = jnp.clip(sgn * (k - bias), 0, vmax - 1)
    v = jnp.where(is_zero, v_sub, v)

    def esize_of(v):
        # floor(log2(v+1)) as exact integer thresholds: esize grows by one at
        # v = 2^j - 1 for each j in [1, 2^h - 1]
        es = jnp.zeros_like(v)
        for j in range(1, (1 << h)):
            es = es + (v >= ((1 << j) - 1)).astype(v.dtype)
        return es

    def mant_round(v):
        """Round mantissa within bucket v; returns (m, mbits, overflow)."""
        es = esize_of(v)
        mbits = nu - h - es
        is_sub = v == v_sub
        e_val = sgn * v
        exp_lo = jnp.where(is_sub, e_val + bias + 1, e_val + bias)
        lead = jnp.where(is_sub, 0, 1)
        # u = mag * 2^(mbits-exp_lo) - lead*2^mbits  (exact, see module doc)
        u = mag * _exp2i(mbits - exp_lo)
        u = u - (lead << mbits).astype(jnp.float32)
        # far-out-of-range x would overflow the int cast; clamp to "overflow"
        u = jnp.minimum(u, 2.0 * (1 << mbits).astype(jnp.float32))
        # half-up via the (exact) fractional part: u + 0.5 is inexact for u
        # just below a tie (0.5 - ulp) and would spuriously round up
        mf = jnp.floor(u)
        m = (mf + (u - mf >= 0.5)).astype(jnp.int32)
        m = jnp.maximum(m, 0)
        ovf = m >= (1 << mbits)
        return m, mbits, ovf

    m, mbits, ovf = mant_round(v)
    at_top = v == v_top
    # overflow moves one bucket toward larger magnitudes (V+1 for SR/SI,
    # V-1 for LR/LI); at the very top it clamps to the max code instead
    v2 = jnp.where(ovf & ~at_top, v + sgn, v)
    es2 = esize_of(v2)
    mbits2 = nu - h - es2
    m2 = jnp.where(ovf, jnp.where(at_top, (1 << mbits2) - 1, 0), m)

    efield = v2 - ((1 << es2) - 1)
    payload = (es2 << (nu - h)) | (efield << mbits2) | m2
    if fmt.signed:
        payload = payload | (sign.astype(jnp.int32) << nu)
    return payload.astype(jnp.uint8 if fmt.n_bits <= 8 else jnp.uint16)


def dequantize_tile_math(codes: jnp.ndarray, fmt: F2PFormat,
                         out_dtype=jnp.float32) -> jnp.ndarray:
    """Branch-free exact F2P decode: codes -> f32 values (unscaled)."""
    nu, h, sgn, vmax, v_sub, v_top, bias = _fmt_consts(fmt)
    c = codes.astype(jnp.int32)
    payload = c & ((1 << nu) - 1)
    es = (payload >> (nu - h)) & ((1 << h) - 1)
    mbits = nu - h - es
    efield = (payload >> mbits) & ((1 << es) - 1)
    v = ((1 << es) - 1) + efield
    m = payload & ((1 << mbits) - 1)
    is_sub = v == v_sub
    e_val = sgn * v
    exp_lo = jnp.where(is_sub, e_val + bias + 1, e_val + bias)
    lead = jnp.where(is_sub, 0, 1)
    sig = ((lead << mbits) + m).astype(jnp.float32)
    val = sig * _exp2i(exp_lo - mbits)
    if fmt.signed:
        sign = (c >> nu) & 1
        val = jnp.where(sign == 1, -val, val)
    return val.astype(out_dtype)


# ---------------------------------------------------------------------------
# LUT decode (host/XLA backend, 8-bit formats)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=128)
def _decode_table(fmt: F2PFormat) -> np.ndarray:
    """All 2^n_bits decoded values (sign included), f32-exact for n<=16."""
    codes = np.arange(1 << fmt.n_bits, dtype=np.int64)
    return fmt.decode(codes).astype(np.float32)


def dequantize_lut(codes: jnp.ndarray, fmt: F2PFormat,
                   out_dtype=jnp.float32) -> jnp.ndarray:
    """Table-gather F2P decode: codes -> f32 values (unscaled).

    Bit-identical to ``dequantize_tile_math`` (every decoded value is exactly
    f32-representable for n_bits <= 16). On CPU/XLA a 256-entry gather beats
    the branch-free bit arithmetic; the dispatch registry selects it for
    8-bit formats on the ``xla`` backend. Never used inside Pallas kernels —
    on TPU the VPU lane arithmetic wins (no gather unit; DESIGN.md §3.3)."""
    table = jnp.asarray(_decode_table(fmt))
    return jnp.take(table, codes.astype(jnp.int32), axis=0).astype(out_dtype)


# ---------------------------------------------------------------------------
# Shared block-scale math: ONE implementation, owned by core.qtensor
# (kernel body == XLA backend == every QTensor producer, bitwise)
# ---------------------------------------------------------------------------
_block_scales = block_scales


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
def _quant_kernel(fmt: F2PFormat, block: int, scale_mode: str,
                  x_ref, codes_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)
    r, ccols = x.shape
    xb = x.reshape(r, ccols // block, block)
    scale = _block_scales(xb, fmt, scale_mode)
    y = (xb / scale[..., None]).astype(jnp.float32).reshape(r, ccols)
    codes_ref[...] = quantize_tile_math(y, fmt)
    scales_ref[...] = scale


def _dequant_kernel(fmt: F2PFormat, block: int, out_dtype,
                    codes_ref, scales_ref, out_ref):
    codes = codes_ref[...]
    r, ccols = codes.shape
    vals = dequantize_tile_math(codes, fmt, jnp.float32)
    vals = vals.reshape(r, ccols // block, block) * scales_ref[...][..., None]
    out_ref[...] = vals.reshape(r, ccols).astype(out_dtype)


def _grid2d(shape, tr, tc):
    r, c = shape
    assert r % tr == 0 and c % tc == 0, f"shape {shape} not tileable ({tr},{tc})"
    return (r // tr, c // tc)


def _col_tile(c: int, block: int) -> int:
    """Column tile for a row of ``c`` elements: 128 scale blocks when the row
    holds a multiple of more (every operand's block stays lane-dense), else
    the whole row (blocks then span the array's last dim)."""
    t = 128 * block
    return t if c > t and c % t == 0 else c


def f2p_quantize_pallas(x: jnp.ndarray, fmt: F2PFormat, *, block: int = 128,
                        scale_mode: str = "f32", interpret: bool | None = None):
    """Blocked F2P quantization of a 2D array. Returns (codes, scales).

    ``interpret=None`` resolves via the dispatch registry: compiled on TPU,
    interpreter elsewhere."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    return _quantize_pallas_jit(x, fmt, block=block, scale_mode=scale_mode,
                                interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode",
                                             "interpret"))
def _quantize_pallas_jit(x: jnp.ndarray, fmt: F2PFormat, *, block: int,
                         scale_mode: str, interpret: bool):
    r, c = x.shape
    tile_c = _col_tile(c, block)
    tile_r = min(TILE_R, r)
    assert c % block == 0
    grid = _grid2d((r, c), tile_r, tile_c)
    code_dtype = jnp.uint8 if fmt.n_bits <= 8 else jnp.uint16
    codes, scales = pl.pallas_call(
        functools.partial(_quant_kernel, fmt, block, scale_mode),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
            pl.BlockSpec((tile_r, tile_c // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), code_dtype),
            jax.ShapeDtypeStruct((r, c // block), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return codes, scales


def f2p_dequantize_pallas(codes: jnp.ndarray, scales: jnp.ndarray,
                          fmt: F2PFormat, *, block: int = 128,
                          out_dtype=jnp.float32, interpret: bool | None = None):
    """Blocked F2P dequantization. ``interpret=None`` resolves via dispatch."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    return _dequantize_pallas_jit(codes, scales, fmt, block=block,
                                  out_dtype=out_dtype,
                                  interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "out_dtype",
                                             "interpret"))
def _dequantize_pallas_jit(codes: jnp.ndarray, scales: jnp.ndarray,
                           fmt: F2PFormat, *, block: int,
                           out_dtype, interpret: bool):
    r, c = codes.shape
    tile_c = _col_tile(c, block)
    tile_r = min(TILE_R, r)
    grid = _grid2d((r, c), tile_r, tile_c)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, fmt, block, out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
            pl.BlockSpec((tile_r, tile_c // block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(codes, scales)
    return out


# ---------------------------------------------------------------------------
# XLA backend (plain jnp under jit — fuses into surrounding HLO) + registry
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode"))
def f2p_quantize_xla(x: jnp.ndarray, fmt: F2PFormat, *, block: int = 128,
                     scale_mode: str = "f32"):
    """Blocked quantize as fused tile math; bitwise-identical to Pallas."""
    x32 = x.astype(jnp.float32)
    r, c = x32.shape
    xb = x32.reshape(r, c // block, block)
    scale = _block_scales(xb, fmt, scale_mode)
    y = (xb / scale[..., None]).astype(jnp.float32).reshape(r, c)
    return quantize_tile_math(y, fmt), scale


@functools.partial(jax.jit, static_argnames=("fmt", "block", "out_dtype"))
def f2p_dequantize_xla(codes: jnp.ndarray, scales: jnp.ndarray,
                       fmt: F2PFormat, *, block: int = 128,
                       out_dtype=jnp.float32):
    """Blocked dequantize as fused tile math; 8-bit formats go through the
    256-entry LUT gather (beats bit arithmetic on CPU — DESIGN.md §3.3)."""
    if fmt.n_bits <= 8:
        vals = dequantize_lut(codes, fmt, jnp.float32)
    else:
        vals = dequantize_tile_math(codes, fmt, jnp.float32)
    r, c = codes.shape
    vals = vals.reshape(r, c // block, block) * scales[..., None]
    return vals.reshape(r, c).astype(out_dtype)


# ---------------------------------------------------------------------------
# Packed variants (DESIGN.md §9): the bit pack/unpack fuses INTO the kernel
# body — packed tensors are quantized and decoded without a byte-aligned
# codes tensor ever hitting HBM. Tile alignment: a column tile of tile_c
# codes occupies exactly packed_words(tile_c, n_bits) uint32 words, which is
# word-exact either when the row fits one tile (tile_c == c: the trailing
# slack words belong to the tile) or when tile_c is a multiple of 32
# (tile_c * n_bits ≡ 0 mod 32 for every n_bits) — _col_tile's multi-tile
# width, 128 * block, is one whenever the block is.
# ---------------------------------------------------------------------------
def _packed_tiles(c: int, block: int, n_bits: int) -> tuple[int, int]:
    """(code tile width, word tile width) for a row of ``c`` codes."""
    tile_c = _col_tile(c, block)
    if tile_c != c and tile_c % 32 != 0:
        raise ValueError(
            f"packed tiling needs a column tile % 32 == 0 (got {tile_c}, "
            f"c={c}) so tile boundaries stay word-aligned")
    return tile_c, packed_words(tile_c, n_bits)


def _quant_packed_kernel(fmt: F2PFormat, block: int, scale_mode: str,
                         x_ref, words_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)
    r, ccols = x.shape
    xb = x.reshape(r, ccols // block, block)
    scale = _block_scales(xb, fmt, scale_mode)
    y = (xb / scale[..., None]).astype(jnp.float32).reshape(r, ccols)
    words_ref[...] = pack_bits_mxu(quantize_tile_math(y, fmt), fmt.n_bits)
    scales_ref[...] = scale


def _dequant_packed_kernel(fmt: F2PFormat, block: int, out_dtype,
                           words_ref, scales_ref, out_ref):
    scales = scales_ref[...]
    r, nblk = scales.shape
    ccols = nblk * block
    codes = unpack_bits_mxu(words_ref[...], fmt.n_bits,
                            ccols).astype(jnp.int32)
    vals = dequantize_tile_math(codes, fmt, jnp.float32)
    vals = vals.reshape(r, nblk, block) * scales[..., None]
    out_ref[...] = vals.reshape(r, ccols).astype(out_dtype)


def f2p_quantize_packed_pallas(x: jnp.ndarray, fmt: F2PFormat, *,
                               block: int = 128, scale_mode: str = "f32",
                               interpret: bool | None = None):
    """Blocked F2P quantization straight into packed words: (words, scales).
    Bitwise: ``pack_bits(f2p_quantize_pallas(x)[0])``."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    return _quantize_packed_pallas_jit(x, fmt, block=block,
                                       scale_mode=scale_mode,
                                       interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode",
                                             "interpret"))
def _quantize_packed_pallas_jit(x: jnp.ndarray, fmt: F2PFormat, *, block: int,
                                scale_mode: str, interpret: bool):
    r, c = x.shape
    assert c % block == 0
    tile_c, tile_w = _packed_tiles(c, block, fmt.n_bits)
    tile_r = min(TILE_R, r)
    grid = _grid2d((r, c), tile_r, tile_c)
    W = grid[1] * tile_w
    words, scales = pl.pallas_call(
        functools.partial(_quant_packed_kernel, fmt, block, scale_mode),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tile_r, tile_w), lambda i, j: (i, j)),
            pl.BlockSpec((tile_r, tile_c // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, W), jnp.uint32),
            jax.ShapeDtypeStruct((r, c // block), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return words, scales


def f2p_dequantize_packed_pallas(words: jnp.ndarray, scales: jnp.ndarray,
                                 fmt: F2PFormat, *, block: int = 128,
                                 out_dtype=jnp.float32,
                                 interpret: bool | None = None):
    """Fused unpack-dequantize of packed words (word tiles stream to VMEM,
    codes exist only in-register)."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    return _dequantize_packed_pallas_jit(words, scales, fmt, block=block,
                                         out_dtype=out_dtype,
                                         interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "out_dtype",
                                             "interpret"))
def _dequantize_packed_pallas_jit(words: jnp.ndarray, scales: jnp.ndarray,
                                  fmt: F2PFormat, *, block: int,
                                  out_dtype, interpret: bool):
    r, c = scales.shape[0], scales.shape[1] * block
    tile_c, tile_w = _packed_tiles(c, block, fmt.n_bits)
    tile_r = min(TILE_R, r)
    grid = _grid2d((r, c), tile_r, tile_c)
    out = pl.pallas_call(
        functools.partial(_dequant_packed_kernel, fmt, block, out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, tile_w), lambda i, j: (i, j)),
            pl.BlockSpec((tile_r, tile_c // block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(words, scales)
    return out


@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode"))
def f2p_quantize_packed_xla(x: jnp.ndarray, fmt: F2PFormat, *,
                            block: int = 128, scale_mode: str = "f32"):
    """Fused tile-math encode + bit pack as one XLA program."""
    codes, scale = f2p_quantize_xla(x, fmt, block=block, scale_mode=scale_mode)
    return pack_bits(codes, fmt.n_bits), scale


@functools.partial(jax.jit, static_argnames=("fmt", "block", "out_dtype"))
def f2p_dequantize_packed_xla(words: jnp.ndarray, scales: jnp.ndarray,
                              fmt: F2PFormat, *, block: int = 128,
                              out_dtype=jnp.float32):
    """Fused unpack + blocked dequantize (npad derives from the scales)."""
    npad = scales.shape[-1] * block
    codes = unpack_bits(words, fmt.n_bits, npad).astype(jnp.int32)
    return f2p_dequantize_xla(codes, scales, fmt, block=block,
                              out_dtype=out_dtype)


@dispatch.register("quantize", dispatch.PALLAS)
def _quantize_pallas_compiled(x, fmt, *, block=128, scale_mode="f32"):
    return f2p_quantize_pallas(x, fmt, block=block, scale_mode=scale_mode,
                               interpret=False)


@dispatch.register("quantize", dispatch.PALLAS_INTERPRET)
def _quantize_pallas_interp(x, fmt, *, block=128, scale_mode="f32"):
    return f2p_quantize_pallas(x, fmt, block=block, scale_mode=scale_mode,
                               interpret=True)


dispatch.register("quantize", dispatch.XLA)(f2p_quantize_xla)


@dispatch.register("dequantize", dispatch.PALLAS)
def _dequantize_pallas_compiled(codes, scales, fmt, *, block=128,
                                out_dtype=jnp.float32):
    return f2p_dequantize_pallas(codes, scales, fmt, block=block,
                                 out_dtype=out_dtype, interpret=False)


@dispatch.register("dequantize", dispatch.PALLAS_INTERPRET)
def _dequantize_pallas_interp(codes, scales, fmt, *, block=128,
                              out_dtype=jnp.float32):
    return f2p_dequantize_pallas(codes, scales, fmt, block=block,
                                 out_dtype=out_dtype, interpret=True)


dispatch.register("dequantize", dispatch.XLA)(f2p_dequantize_xla)


@dispatch.register("quantize_packed", dispatch.PALLAS)
def _quantize_packed_pallas_compiled(x, fmt, *, block=128, scale_mode="f32"):
    return f2p_quantize_packed_pallas(x, fmt, block=block,
                                      scale_mode=scale_mode, interpret=False)


@dispatch.register("quantize_packed", dispatch.PALLAS_INTERPRET)
def _quantize_packed_pallas_interp(x, fmt, *, block=128, scale_mode="f32"):
    return f2p_quantize_packed_pallas(x, fmt, block=block,
                                      scale_mode=scale_mode, interpret=True)


dispatch.register("quantize_packed", dispatch.XLA)(f2p_quantize_packed_xla)


@dispatch.register("dequantize_packed", dispatch.PALLAS)
def _dequantize_packed_pallas_compiled(words, scales, fmt, *, block=128,
                                       out_dtype=jnp.float32):
    return f2p_dequantize_packed_pallas(words, scales, fmt, block=block,
                                        out_dtype=out_dtype, interpret=False)


@dispatch.register("dequantize_packed", dispatch.PALLAS_INTERPRET)
def _dequantize_packed_pallas_interp(words, scales, fmt, *, block=128,
                                     out_dtype=jnp.float32):
    return f2p_dequantize_packed_pallas(words, scales, fmt, block=block,
                                        out_dtype=out_dtype, interpret=True)


dispatch.register("dequantize_packed", dispatch.XLA)(f2p_dequantize_packed_xla)
