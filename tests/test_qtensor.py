"""QTensor (core/qtensor.py): pytree behavior, codec parity vs the retained
f64 grid oracle, KV-cache migration parity, checkpoint bit-exactness on
QTensor leaves, residual sentinels, and the FL convergence smoke test."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qtensor as QT
from repro.core import quantize as Q
from repro.core.f2p import F2PFormat, Flavor
from repro.core.qtensor import QTensor

FMT8 = F2PFormat(8, 2, Flavor.SR, signed=True)

PARITY_FMTS = [
    F2PFormat(8, 2, Flavor.SR, signed=True),
    F2PFormat(8, 2, Flavor.LR, signed=True),
    F2PFormat(8, 1, Flavor.SI, signed=False),
    F2PFormat(8, 2, Flavor.LI, signed=False),
    F2PFormat(16, 2, Flavor.SR, signed=True),
    F2PFormat(16, 1, Flavor.LR, signed=True),
]


def _data(shape, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, scale, size=shape).astype(np.float32)
    x.flat[::7] = 0.0
    x.flat[3::11] *= 1e-3
    x.flat[5::13] *= 1e3
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# pytree protocol
# ---------------------------------------------------------------------------
def test_pytree_roundtrip_eager():
    qt = QT.quantize(_data((4, 100)), FMT8, block=32)
    leaves, td = jax.tree.flatten(qt)
    assert len(leaves) == 2  # codes, scales — nothing else is dynamic
    back = jax.tree.unflatten(td, leaves)
    assert isinstance(back, QTensor)
    assert (back.fmt, back.block, back.shape) == (qt.fmt, qt.block, qt.shape)
    np.testing.assert_array_equal(np.asarray(back.codes), np.asarray(qt.codes))


def test_pytree_roundtrip_under_jit():
    x = _data((8, 256))

    @jax.jit
    def f(x):
        qt = QT.quantize(x, FMT8, block=128)
        # QTensor crosses the jit boundary as a pytree output
        return qt

    qt = f(x)
    assert isinstance(qt, QTensor)
    y = qt.dequantize()
    assert y.shape == x.shape

    @jax.jit
    def g(qt):  # ... and as an input; static aux hashes into the cache key
        return qt.dequantize()

    np.testing.assert_array_equal(np.asarray(g(qt)), np.asarray(y))


def test_pytree_roundtrip_under_shard_map():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    x = _data((8, 256))

    def body(xs):
        qt = QT.quantize(xs, FMT8, block=128)
        return qt.dequantize()

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    want = QT.quantize(x, FMT8, block=128).dequantize()
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(want))


def test_scan_and_broadcast_leading_dims():
    """The KV-cache lifecycle restructures leading dims (broadcast_to a
    group axis, scan-unstack); logical_shape must follow the live leaves."""
    qt = QT.quantize(_data((2, 6, 4, 16)), FMT8, block=16)
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + a.shape), qt)
    assert stacked.logical_shape == (3, 2, 6, 4, 16)
    un = jax.tree.map(lambda a: a[0], stacked)
    np.testing.assert_array_equal(np.asarray(un.dequantize()),
                                  np.asarray(qt.dequantize()))


# ---------------------------------------------------------------------------
# codec parity vs the f64 grid oracle (odd last dims exercise padding)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", PARITY_FMTS, ids=str)
@pytest.mark.parametrize("shape,block", [((4, 128), 128), ((3, 100), 32),
                                         ((2, 5, 77), 16), ((513,), 128)])
def test_quantize_matches_grid_oracle(fmt, shape, block):
    """Codes+values agree with core.quantize.block_quantize (the independent
    f64 numpy oracle) wherever the f32/f64 scale division rounds alike; the
    dequantized values always stay within the per-block error bound."""
    x = _data(shape, seed=hash((fmt.n_bits, shape)) % 1000)
    if not fmt.signed:
        x = jnp.abs(x)
    qt = QT.quantize(x, fmt, block=block)
    n = shape[-1]
    npad = -(-n // block) * block
    assert qt.codes.shape == shape[:-1] + (npad,)
    assert qt.scales.shape == shape[:-1] + (npad // block,)
    y = np.asarray(qt.dequantize())
    assert y.shape == tuple(shape)

    # oracle on the padded array (f64 path, independent implementation)
    xp = np.zeros(shape[:-1] + (npad,), np.float64)
    xp[..., :n] = np.asarray(x, np.float64)
    bq = Q.block_quantize(xp, fmt, block=block)
    yo = Q.block_dequantize(bq)[..., :n]
    # scales differ only by f32-vs-f64 division rounding; values must agree
    # to within one quantization step of the per-block scale
    step = np.max(np.diff(fmt.payload_grid))
    bound = np.asarray(qt.scales, np.float64).max() * step
    assert np.max(np.abs(y - yo)) <= bound + 1e-7


@pytest.mark.parametrize("fmt", PARITY_FMTS[:2], ids=str)
def test_backends_bitwise_identical(fmt):
    x = _data((16, 384), seed=3)
    qx = QT.quantize(x, fmt, block=128, backend="xla")
    qp = QT.quantize(x, fmt, block=128, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(qx.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(qx.scales), np.asarray(qp.scales))
    np.testing.assert_array_equal(
        np.asarray(QT.dequantize(qx, backend="xla")),
        np.asarray(QT.dequantize(qx, backend="pallas_interpret")))


def test_from_parts_zero_copy_and_validation():
    qt = QT.quantize(_data((4, 100)), FMT8, block=32)
    re = QTensor.from_parts(qt.codes, qt.scales, qt.fmt, qt.block, qt.shape)
    assert re.codes is qt.codes and re.scales is qt.scales  # zero-copy
    with pytest.raises(ValueError, match="codes last dim"):
        QTensor.from_parts(qt.codes[..., :64], qt.scales, FMT8, 32, (4, 100))
    with pytest.raises(ValueError, match="scales last dim"):
        QTensor.from_parts(qt.codes, qt.scales[..., :2], FMT8, 32, (4, 100))
    with pytest.raises(ValueError, match="leading dims"):
        QTensor.from_parts(qt.codes, qt.scales[:2], FMT8, 32, (4, 100))


def test_scale_by_folds_into_dequant():
    qt = QT.quantize(_data((4, 128)), FMT8)
    np.testing.assert_allclose(np.asarray(qt.scale_by(0.25).dequantize()),
                               np.asarray(qt.dequantize()) * 0.25,
                               rtol=1e-6, atol=1e-7)


def test_dynamic_update_writes_both_leaves():
    base = QT.quantize(jnp.zeros((2, 8, 4, 16)), FMT8, block=16)
    new = QT.quantize(_data((2, 3, 4, 16), seed=9), FMT8, block=16)
    upd = base.dynamic_update(new, 2, axis=1)
    out = np.asarray(upd.dequantize())
    np.testing.assert_array_equal(out[:, 2:5], np.asarray(new.dequantize()))
    assert np.all(out[:, :2] == 0) and np.all(out[:, 5:] == 0)
    with pytest.raises(ValueError, match="blocked axis"):
        base.dynamic_update(new, 0, axis=-1)


# ---------------------------------------------------------------------------
# KV-cache migration parity
# ---------------------------------------------------------------------------
def test_kv_cache_parity_with_pre_migration_math():
    """QTensor cache writes reproduce the seed's inline KV math bit-for-bit:
    per-(position, head) scale over head_dim == block = head_dim."""
    from repro.kernels.f2p_quant import quantize_tile_math
    from repro.models import attention as A

    k = _data((2, 6, 2, 16), seed=4)
    qt = A.quantize_kv(k)
    # pre-migration inline math (copied from the seed implementation)
    absmax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0,
                      absmax * jnp.float32(1.0 / A.KV_FMT.max_value), 1.0)
    codes = quantize_tile_math((k / scale).astype(jnp.float32), A.KV_FMT)
    np.testing.assert_array_equal(np.asarray(qt.codes), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(qt.scales), np.asarray(scale))
    np.testing.assert_array_equal(
        np.asarray(qt.dequantize(jnp.float32)),
        np.asarray(A.dequantize_kv(qt, jnp.float32)))


def test_quantized_cache_decode_roundtrip():
    """Prefill+decode through the QTensor cache matches the dense cache
    closely (the migration must not move the quantization error)."""
    from repro.models import decode_step, init_caches, init_params, prefill
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128,
                      dtype="float32", remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 128)
    caches = init_caches(cfg, 2, 9)
    _, caches = prefill(params, {"tokens": toks[:, :8]}, cfg, caches)
    lg, _ = decode_step(params, toks[:, 8:], jnp.int32(8), caches, cfg)

    qcaches = init_caches(cfg, 2, 9, quantized_kv=True)
    assert isinstance(qcaches["b0"]["k"], QTensor)
    _, qcaches = prefill(params, {"tokens": toks[:, :8]}, cfg, qcaches)
    lgq, _ = decode_step(params, toks[:, 8:], jnp.int32(8), qcaches, cfg)
    err = np.abs(np.asarray(lgq) - np.asarray(lg)).max()
    assert err < 0.25 * np.asarray(lg).std(), err


# ---------------------------------------------------------------------------
# checkpoint: QTensor leaves round-trip bit-exactly; lazy restore
# ---------------------------------------------------------------------------
def test_checkpoint_qtensor_leaves_bit_exact(tmp_path):
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    os.makedirs(d)
    tree = {"kv": QT.quantize(_data((4, 6, 2, 16), seed=7), FMT8, block=16),
            "raw": jnp.arange(8.0)}
    checkpoint.save(d, 1, tree)
    restored, step = checkpoint.restore(d, tree)
    assert step == 1 and isinstance(restored["kv"], QTensor)
    np.testing.assert_array_equal(np.asarray(restored["kv"].codes),
                                  np.asarray(tree["kv"].codes))
    np.testing.assert_array_equal(np.asarray(restored["kv"].scales),
                                  np.asarray(tree["kv"].scales))
    assert restored["kv"].fmt == tree["kv"].fmt
    assert restored["kv"].shape == tree["kv"].shape


def test_checkpoint_lazy_restore_returns_qtensor(tmp_path):
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    os.makedirs(d)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)}
    checkpoint.save(d, 0, tree, compress=True, min_size=1024)
    eager, _ = checkpoint.restore(d, tree)
    lazy, _ = checkpoint.restore(d, tree, lazy=True)
    assert isinstance(lazy["w"], QTensor)
    np.testing.assert_array_equal(
        np.asarray(lazy["w"].dequantize(backend="xla")),
        np.asarray(eager["w"]))
    # compressed payload really is the QTensor wire size
    assert lazy["w"].nbytes < tree["w"].size * 4 * 0.6


def test_checkpoint_compress_never_recompresses_qtensor_leaves(tmp_path):
    """compress=True must leave embedded QTensor leaves alone: the f32
    scales of a big QTensor would otherwise pass the float/min_size test and
    take a lossy F2P16 round-trip (lossy-on-lossy, no longer bit-exact)."""
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    os.makedirs(d)
    qt = QT.quantize(_data((70000, 8), seed=11), FMT8, block=8)
    assert qt.scales.size >= 65536  # would qualify for compression
    checkpoint.save(d, 0, {"kv": qt}, compress=True)
    restored, _ = checkpoint.restore(d, {"kv": qt})
    np.testing.assert_array_equal(np.asarray(restored["kv"].scales),
                                  np.asarray(qt.scales))
    np.testing.assert_array_equal(np.asarray(restored["kv"].codes),
                                  np.asarray(qt.codes))


def test_checkpoint_compress_narrow_leaf_never_expands(tmp_path):
    """A narrow-last-dim leaf ([N, 1]: 2B code + 4B scale per element vs 4B
    raw) would EXPAND under the codec — it must ship raw (and therefore
    restore bit-exactly). Wide leaves still shrink."""
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    os.makedirs(d)
    rng = np.random.default_rng(0)
    tree = {"narrow": jnp.asarray(rng.normal(size=(70000, 1)), jnp.float32),
            "wide": jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)}
    checkpoint.save(d, 0, tree, compress=True)
    size = os.path.getsize(os.path.join(d, "step_0", "data.bin"))
    raw = 70000 * 4 + 512 * 256 * 4
    assert size < raw, (size, raw)  # never larger than uncompressed
    restored, _ = checkpoint.restore(d, tree)
    np.testing.assert_array_equal(np.asarray(restored["narrow"]),
                                  np.asarray(tree["narrow"]))  # raw path
    err = np.abs(np.asarray(restored["wide"]) - np.asarray(tree["wide"]))
    assert 0 < err.max() < 2e-3  # wide leaf really took the codec


def test_checkpoint_restore_shardings_with_qtensor_leaves(tmp_path):
    """restore(shardings=...) must place a QTensor leaf as a whole against
    one sharding entry (lazy restore on a mesh is the serving path)."""
    from jax.sharding import SingleDeviceSharding
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    os.makedirs(d)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)}
    checkpoint.save(d, 0, tree, compress=True, min_size=1024)
    sh = {"w": SingleDeviceSharding(jax.devices()[0])}
    lazy, _ = checkpoint.restore(d, tree, shardings=sh, lazy=True)
    assert isinstance(lazy["w"], QTensor)
    eager, _ = checkpoint.restore(d, tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(lazy["w"].dequantize()),
                                  np.asarray(eager["w"]))


def test_ops_f2p_dequantize_legacy_2d_layout():
    """The compat entry point still accepts the kernels' collapsed 2D codes
    (merged leading dims, rows padded to the sublane) + an ND out_shape."""
    from repro.kernels import f2p_quant as K
    from repro.kernels import ops

    x = _data((3, 128), seed=13)  # 3 rows -> kernel pads to 8
    x2 = jnp.pad(x, ((0, 5), (0, 0)))
    codes, scales = K.f2p_quantize_pallas(x2, FMT8, interpret=True)
    y = ops.f2p_dequantize(codes, scales, FMT8, out_shape=(3, 128))
    assert y.shape == (3, 128)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(QT.quantize(x, FMT8).dequantize()))
    # merged leading dims reshape back too
    x4 = _data((4, 16, 128), seed=14)
    q2 = QT.quantize(x4.reshape(64, 128), FMT8)
    y4 = ops.f2p_dequantize(q2.codes, q2.scales, FMT8, out_shape=(4, 16, 128))
    assert y4.shape == (4, 16, 128)


# ---------------------------------------------------------------------------
# residual sentinels (optim.compress satellite)
# ---------------------------------------------------------------------------
def test_small_leaf_residual_is_none_not_scalar():
    from repro.optim import CompressionConfig, init_residuals

    ccfg = CompressionConfig(min_size=64)
    params = {"big": jnp.zeros((8, 16)), "small": jnp.zeros((4,))}
    r = init_residuals(params, ccfg)
    assert r["small"] is None
    assert r["big"].shape == (8, 16)


def test_compress_decompress_asserts_shape_agreement():
    from repro.optim import CompressionConfig, compress_decompress

    ccfg = CompressionConfig(min_size=64)
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                          jnp.float32)}
    with pytest.raises(ValueError, match="residual shape"):
        compress_decompress(g, {"w": jnp.zeros((8, 8), jnp.float32)}, ccfg)
    # lowering min_size with a stale None residual must NOT silently
    # broadcast: the leaf just stays uncompressed
    out, res = compress_decompress(g, {"w": None},
                                   CompressionConfig(min_size=4))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(g["w"]))
    assert res["w"] is None


# ---------------------------------------------------------------------------
# FL convergence smoke (paper's federated-learning claim)
# ---------------------------------------------------------------------------
def test_fl_quantized_matches_f32_fedavg():
    from repro.fl import ClientConfig, FedAvgConfig, run_fed_avg, toy_task

    task = toy_task()
    hist = {}
    for name, compress in (("f32", False), ("q", True)):
        fcfg = FedAvgConfig(
            n_clients=2, rounds=5,
            client=ClientConfig(local_steps=2, lr=0.1, compress=compress))
        hist[name] = run_fed_avg(fcfg, task)
    f32_final = hist["f32"]["eval_loss"][-1]
    q_final = hist["q"]["eval_loss"][-1]
    # converging at all...
    assert q_final < hist["q"]["eval_loss"][0] - 0.5
    # ...and at parity with uncompressed fed-avg (the acceptance bar)
    assert q_final <= 1.05 * f32_final, (q_final, f32_final)
    # wire bytes actually shrink
    assert (hist["f32"]["wire_bytes_per_round"][-1]
            >= 3.5 * hist["q"]["wire_bytes_per_round"][-1])
