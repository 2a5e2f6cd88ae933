"""Bit-packed F2P storage (DESIGN.md §9, ISSUE 5).

Covers: pack/unpack round-trip properties (n_bits 1-19 x odd lengths x
word-boundary-straddling fields, jnp vs numpy twins bit-identical),
packed-vs-unpacked bitwise code identity through quantize / dequant-matmul /
the KV cache / checkpoints, the honest ``nbytes``/wire accounting (one
canonical ``packed_nbytes`` everywhere), and packed FL round parity with the
unpacked loss curve.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    sys.path.insert(0, os.path.dirname(__file__))
    from _hypofallback import given, settings, st

from repro.core import qtensor as QT
from repro.core.f2p import F2PFormat, Flavor
from repro.core.qtensor import QTensor
from repro.kernels import bits as B

FMT8 = F2PFormat(8, 2, Flavor.SR, signed=True)
FMT6 = F2PFormat(6, 2, Flavor.SR, signed=True)
FMT10 = F2PFormat(10, 2, Flavor.LR, signed=True)


def _data(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, scale, size=shape).astype(np.float32)
    x.flat[::7] = 0.0
    x.flat[3::11] *= 1e-3
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# pack/unpack primitives
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(n_bits=st.integers(1, 19), n=st.integers(1, 300),
       seed=st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip_property(n_bits, n, seed):
    """Round trip across widths x odd lengths x straddling fields; jnp and
    numpy twins agree bit-for-bit, and word counts match packed_words."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << n_bits, size=(2, n)).astype(np.uint32)
    pw_np = B.pack_bits_np(c, n_bits)
    assert pw_np.shape == (2, B.packed_words(n, n_bits))
    assert pw_np.dtype == np.uint32
    pw_j = np.asarray(B.pack_bits_jit(jnp.asarray(c), n_bits))
    assert (pw_j == pw_np).all()
    u_np = B.unpack_bits_np(pw_np, n_bits, n)
    u_j = np.asarray(B.unpack_bits_jit(jnp.asarray(pw_np), n_bits, n))
    assert (u_np == c).all()
    assert (u_j == c).all()


def test_pack_layout_is_little_endian_dense():
    """Pin the exact wire layout: element i occupies bits [i*n, (i+1)*n) of
    the row stream, LSB first, stream bit b at bit b%32 of word b//32."""
    c = np.array([[0b101011, 0b110010, 0b011111, 0b000001, 0b100000,
                   0b010101]], np.uint32)
    pw = B.pack_bits_np(c, 6)
    stream = 0
    for i, v in enumerate(c[0]):
        stream |= int(v) << (6 * i)
    assert int(pw[0, 0]) == (stream & 0xFFFFFFFF)
    assert int(pw[0, 1]) == (stream >> 32)  # 36 bits: straddles word 0 -> 1


def test_pack_masks_out_of_range_codes_identically():
    """An oversized code must not bleed into its neighbor's field, and the
    jnp / numpy twins must agree on that masking (both fast and general
    paths) — a host producer with a stale wide buffer gets the same words
    as the device path, not silent corruption."""
    for n_bits in (8, 6):  # 32 % 8 == 0 fast path; 6 = general path
        c = np.array([[300, 1, 2, 3]], np.uint32)
        pn = B.pack_bits_np(c, n_bits)
        pj = np.asarray(B.pack_bits_jit(jnp.asarray(c), n_bits))
        assert (pn == pj).all()
        masked = c & ((1 << n_bits) - 1)
        assert (B.unpack_bits_np(pn, n_bits, 4) == masked).all()


def test_pack_rows_never_share_words():
    """Each last-axis row packs independently — slicing a leading axis of
    the packed buffer equals packing the sliced rows."""
    c = np.arange(3 * 50, dtype=np.uint32).reshape(3, 50) & 0x3F
    pw = B.pack_bits_np(c, 6)
    for r in range(3):
        assert (pw[r] == B.pack_bits_np(c[r], 6)).all()


def test_unpack_rejects_short_buffer():
    with pytest.raises(ValueError, match="cannot hold"):
        B.unpack_bits_np(np.zeros((2,), np.uint32), 6, 20)
    with pytest.raises(ValueError, match="cannot hold"):
        B.unpack_bits_jit(jnp.zeros((2,), jnp.uint32), 6, 20)


def test_packed_nbytes_is_word_granular():
    assert B.packed_nbytes(128, 6) == 4 * 24   # 768 bits = 24 words exactly
    assert B.packed_nbytes(100, 6) == 4 * 19   # 600 bits -> 19 words
    assert B.packed_nbytes(1, 1) == 4          # never less than one word
    assert B.packed_words(0, 8) == 0


# ---------------------------------------------------------------------------
# packed QTensor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [FMT6, FMT8, FMT10])
@pytest.mark.parametrize("shape,block", [((4, 100), 32), ((2, 3, 64), 64),
                                         ((128, 384), 128)])
def test_quantize_packed_bitwise_identity(fmt, shape, block):
    """quantize(packed=True) == quantize().pack() bit-for-bit, and both
    dequantize to the identical values (xla backend)."""
    x = _data(shape, seed=fmt.n_bits)
    qt = QT.quantize(x, fmt, block=block, backend="xla")
    qp = QT.quantize(x, fmt, block=block, backend="xla", packed=True)
    assert qp.packed and qp.codes.dtype == jnp.uint32
    assert (np.asarray(qt.pack().codes) == np.asarray(qp.codes)).all()
    assert (np.asarray(qp.unpack().codes) == np.asarray(qt.codes)).all()
    assert (np.asarray(qt.scales) == np.asarray(qp.scales)).all()
    assert (np.asarray(qt.dequantize()) == np.asarray(qp.dequantize())).all()


def test_packed_backend_parity_pallas_interpret():
    fmt = FMT8
    x = _data((16, 256), seed=3)
    qx = QT.quantize(x, fmt, block=128, backend="xla", packed=True)
    qi = QT.quantize(x, fmt, block=128, backend="pallas_interpret",
                     packed=True)
    assert (np.asarray(qx.codes) == np.asarray(qi.codes)).all()
    assert (np.asarray(qx.scales) == np.asarray(qi.scales)).all()
    di = QT.dequantize(qi, backend="pallas_interpret")
    dx = QT.dequantize(qx, backend="xla")
    assert (np.asarray(di) == np.asarray(dx)).all()


def test_packed_nbytes_honest_and_canonical():
    """6-bit packed <= 0.80x unpacked (the ISSUE-5 acceptance), and nbytes
    equals the canonical packed_nbytes formula exactly."""
    x = _data((256, 1024), seed=1)
    qt = QT.quantize(x, FMT6, block=128, backend="xla")
    qp = qt.pack()
    assert qp.nbytes / qt.nbytes <= 0.80
    rows = 256
    expect = rows * B.packed_nbytes(1024, 6) + qp.scales.size * 4
    assert qp.nbytes == expect


def test_from_parts_packed_validation():
    qp = QT.quantize(_data((4, 100)), FMT6, block=32, backend="xla",
                     packed=True)
    re = QTensor.from_parts(qp.codes, qp.scales, FMT6, 32, (4, 100),
                            packed=True)
    assert (np.asarray(re.dequantize()) == np.asarray(qp.dequantize())).all()
    with pytest.raises(ValueError, match="packed codes"):   # word count
        QTensor.from_parts(qp.codes[..., :-1], qp.scales, FMT6, 32, (4, 100),
                           packed=True)
    with pytest.raises(ValueError, match="uint32"):          # dtype
        QTensor.from_parts(qp.codes.astype(jnp.int32), qp.scales, FMT6, 32,
                           (4, 100), packed=True)
    with pytest.raises(ValueError, match="last dim"):        # packed flag off
        QTensor.from_parts(qp.codes, qp.scales, FMT6, 32, (4, 100))


def test_packed_pytree_and_jit_static_aux():
    """packed is static aux: it survives flatten/unflatten and packed vs
    unpacked inputs compile separately instead of miscomputing."""
    qp = QT.quantize(_data((8, 128)), FMT8, block=128, packed=True)
    leaves, treedef = jax.tree.flatten(qp)
    re = jax.tree.unflatten(treedef, leaves)
    assert re.packed and re.fmt == qp.fmt

    calls = []

    @jax.jit
    def f(q):
        calls.append(1)
        return q.dequantize()

    qt = qp.unpack()
    a, b = f(qp), f(qt)
    assert (np.asarray(a) == np.asarray(b)).all()
    assert len(calls) == 2  # distinct cache entries


def test_dynamic_update_packed_mismatch_raises():
    qp = QT.quantize(_data((4, 8, 64)), FMT8, block=64, packed=True)
    qu = QT.quantize(_data((1, 8, 64)), FMT8, block=64, packed=False)
    with pytest.raises(ValueError, match="packed"):
        qp.dynamic_update(qu, 0, axis=0)
    slab = QT.quantize(_data((1, 8, 64), seed=9), FMT8, block=64, packed=True)
    out = qp.dynamic_update(slab, 2, axis=0)
    assert (np.asarray(out.codes[2]) == np.asarray(slab.codes[0])).all()


# ---------------------------------------------------------------------------
# consumers: matmul, KV cache, checkpoint, FL
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_bits", [6, 8, 10])
def test_packed_dequant_matmul_identity(n_bits):
    from repro.kernels import f2p_matmul as MM

    fmt = F2PFormat(n_bits, 2, Flavor.SR, signed=True)
    rng = np.random.default_rng(n_bits)
    x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    codes, scales = MM.quantize_weight(w, fmt)
    words, scales_p = MM.quantize_weight(w, fmt, packed=True)
    assert (np.asarray(scales) == np.asarray(scales_p)).all()
    assert (np.asarray(B.pack_bits_jit(codes, n_bits))
            == np.asarray(words)).all()
    y = np.asarray(MM.dequant_matmul(x, codes, scales, fmt=fmt,
                                     backend="xla"))
    yp = np.asarray(MM.dequant_matmul(x, words, scales, fmt=fmt,
                                      backend="xla", packed=True))
    assert (y == yp).all()
    yi = np.asarray(MM.dequant_matmul(x, words, scales, fmt=fmt,
                                      backend="pallas_interpret",
                                      packed=True))
    np.testing.assert_allclose(yi, y, rtol=1e-5, atol=1e-5)


def test_packed_kv_cache_decode_parity():
    """Packed and unpacked quantized KV caches produce bitwise-identical
    decode logits (fused unpack in the read path, word-aligned slab
    writes)."""
    from repro.configs import smoke_config
    from repro.models import decode_step, init_caches, init_params, prefill

    cfg = smoke_config("llama3_2_3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B_, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B_, S + 2), 0,
                              cfg.vocab_size)
    outs = {}
    for pk in (False, True):
        caches = init_caches(cfg, B_, 16, quantized_kv=True, packed_kv=pk)
        _, caches = prefill(params, {"tokens": toks[:, :S]}, cfg, caches)
        for i in range(2):  # decode writes exercise dynamic_update slabs
            lg, caches = decode_step(params, toks[:, S + i:S + i + 1],
                                     jnp.int32(S + i), caches, cfg)
        outs[pk] = np.asarray(lg)
    assert (outs[True] == outs[False]).all()


def test_packed_kv_empty_cache_decodes_to_zero():
    from repro.configs import smoke_config
    from repro.models.attention import init_cache

    cfg = smoke_config("llama3_2_3b")
    for fmt in (FMT8, F2PFormat(8, 2, Flavor.LR, signed=True)):
        c = init_cache(cfg, 1, 4, True, jnp.float32, fmt=fmt, packed=True)
        assert c["k"].packed
        assert (np.asarray(c["k"].dequantize()) == 0.0).all()


def test_checkpoint_packed_roundtrip_and_legacy(tmp_path):
    from repro.train import checkpoint as CK

    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(0, 0.1, (256, 192)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32)}
    d = str(tmp_path)
    CK.save(d, 1, tree, compress=True, min_size=1024, packed=True)
    CK.save(d, 2, tree, compress=True, min_size=1024, packed=False)
    lazy_p, _ = CK.restore(d, tree, step=1, lazy=True)
    assert lazy_p["w"].packed and lazy_p["w"].codes.dtype == np.uint32
    out_p, _ = CK.restore(d, tree, step=1)
    out_u, _ = CK.restore(d, tree, step=2)    # legacy-style unpacked entry
    assert (out_p["w"] == out_u["w"]).all()   # bit-identical decode
    assert (out_p["b"] == tree["b"]).all()    # raw leaf untouched
    # index carries the flag; unpacked entries restore with packed=False
    import json

    with open(os.path.join(d, "step_1", "index.json")) as f:
        idx = json.load(f)["leaves"]
    w_key = [k for k in idx if "w" in k][0]
    assert idx[w_key]["packed"] is True


def test_checkpoint_packed_6bit_shrinks(tmp_path):
    from repro.autotune.policy import FormatPolicy, PolicyRule
    from repro.train import checkpoint as CK

    rng = np.random.default_rng(1)
    tree = {"w": rng.normal(0, 0.1, (512, 256)).astype(np.float32)}
    pol = FormatPolicy(rules=(PolicyRule("ckpt/*", "f2p_sr_2_6s"),))
    d = str(tmp_path)
    p1 = CK.save(d, 1, tree, compress=True, min_size=1024, packed=True,
                 policy=pol)
    p2 = CK.save(d, 2, tree, compress=True, min_size=1024, packed=False,
                 policy=pol)
    s1 = os.path.getsize(os.path.join(p1, "data.bin"))
    s2 = os.path.getsize(os.path.join(p2, "data.bin"))
    assert s1 <= 0.80 * s2
    o1, _ = CK.restore(d, tree, step=1)
    o2, _ = CK.restore(d, tree, step=2)
    assert (o1["w"] == o2["w"]).all()


def test_compressed_psum_packed_parity():
    from jax import shard_map as smap
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.optim.compress import CompressionConfig, compressed_psum

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    g = _data((64, 192), seed=5, scale=1e-3)
    outs = {}
    for pk in (False, True):
        ccfg = CompressionConfig(packed=pk)
        f = jax.jit(smap(lambda gg: compressed_psum(gg, "dp", ccfg),
                         mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))
        outs[pk] = np.asarray(f(g))
    assert (outs[True] == outs[False]).all()


def test_fl_packed_round_parity_and_wire():
    """Packed FL rounds track the unpacked loss curve exactly at 8 bits
    (bitwise-identical codec) and the wire accounting goes through the one
    canonical packed_nbytes formula."""
    from repro.fl import ClientConfig, FedAvgConfig, run_fed_avg, toy_task
    from repro.fl.server import wire_bytes

    task = toy_task()
    hists = {}
    for pk in (False, True):
        fcfg = FedAvgConfig(n_clients=2, rounds=2,
                            client=ClientConfig(compress=True, packed=pk))
        hists[pk] = run_fed_avg(fcfg, task)
    assert hists[True]["eval_loss"] == hists[False]["eval_loss"]
    # 8-bit packs 4 codes per word: byte count unchanged, bit-for-bit
    assert (hists[True]["wire_bytes_per_round"]
            == hists[False]["wire_bytes_per_round"])

    # a 6-bit leaf really costs 6 bits on the wire
    qt = QT.quantize(_data((32, 128)), FMT6, block=128, packed=True)
    assert wire_bytes({"d": qt}) == qt.nbytes
    assert qt.nbytes == 32 * B.packed_nbytes(128, 6) + 32 * 4


def test_env_default_resolution(monkeypatch):
    from repro.core.qtensor import packed_default, resolve_packed

    monkeypatch.delenv("F2P_PACKED", raising=False)
    assert packed_default() is False
    assert resolve_packed(None) is False
    assert resolve_packed(True) is True
    monkeypatch.setenv("F2P_PACKED", "1")
    assert packed_default() is True
    assert resolve_packed(None) is True
    assert resolve_packed(False) is False
