"""Backend dispatch registry (DESIGN.md §3.4) + LUT decode variant."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.f2p import F2PFormat, Flavor
from repro.kernels import dispatch, ops
from repro.kernels import f2p_counter  # noqa: F401  (registers counter ops)
from repro.kernels import f2p_matmul as FM
from repro.kernels import f2p_quant as K

FMT8 = F2PFormat(8, 2, Flavor.SR, signed=True)
FMT16 = F2PFormat(16, 2, Flavor.SR, signed=True)


def _data(shape=(16, 512), seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 3, size=shape).astype(np.float32)
    x.flat[::7] = 0.0
    x.flat[3::11] *= 1e-3
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# resolution policy
# ---------------------------------------------------------------------------
def test_all_ops_register_all_backends():
    for op in ("quantize", "dequantize", "dequant_matmul",
               "counter_advance", "counter_estimate"):
        assert set(dispatch.implementations(op)) == set(dispatch.BACKENDS), op


def test_default_resolution_matches_platform(monkeypatch):
    # the DEFAULT policy under test — shield it from an ambient override
    # (the CI kernel-parity cell exports F2P_BACKEND=pallas_interpret)
    monkeypatch.delenv("F2P_BACKEND", raising=False)
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert dispatch.resolve_backend() == expect


def test_resolution_inside_trace_is_xla_and_trace_safe(monkeypatch):
    """Inside a trace the policy is the platform's: compiled pallas on a
    TPU (the jitted serving round must run the kernels), xla elsewhere."""
    monkeypatch.delenv("F2P_BACKEND", raising=False)
    seen = []

    @jax.jit
    def f(x):
        seen.append(dispatch.resolve_backend())
        return x

    f(jnp.zeros(()))
    assert seen == ["pallas" if jax.default_backend() == "tpu" else "xla"]


@pytest.mark.parametrize("platform,op,want", [
    ("tpu", "attention_paged", "pallas"),
    ("tpu", "quantize_packed", "pallas"),
    ("tpu", "only_xla_op_on_tpu", "xla"),
    ("cpu", "attention_paged", "xla"),
])
def test_trace_resolution_by_platform(monkeypatch, platform, op, want):
    """The rule itself, platform stubbed: in a trace, an op with a Pallas
    kernel resolves to compiled pallas on a TPU; an op without one, and
    every op off the TPU, resolves to xla."""
    from repro.kernels import f2p_attention, f2p_quant  # noqa: F401

    dispatch.register("only_xla_op_on_tpu", "xla")(lambda: None)
    monkeypatch.delenv("F2P_BACKEND", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: platform)
    seen = []

    def f(x):
        seen.append(dispatch.resolve_backend(op=op))
        return x

    jax.make_jaxpr(f)(jnp.zeros(()))
    assert seen == [want]


def test_env_override(monkeypatch):
    monkeypatch.setenv("F2P_BACKEND", "pallas_interpret")
    assert dispatch.resolve_backend() == "pallas_interpret"


def test_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("F2P_BACKEND", "pallas_interpret")
    assert dispatch.resolve_backend("xla") == "xla"


def test_aliases_and_unknown():
    assert dispatch.resolve_backend("interpret") == "pallas_interpret"
    assert dispatch.resolve_backend("jit") == "xla"
    with pytest.raises(ValueError, match="unknown backend"):
        dispatch.resolve_backend("cuda")


def test_missing_op_impl_raises():
    @dispatch.register("only_xla_op", "xla")
    def impl():
        pass

    with pytest.raises(ValueError, match="no 'pallas'"):
        dispatch.lookup("only_xla_op", "pallas")


def test_use_pallas_legacy_mapping():
    x = _data()
    q_legacy = ops.f2p_quantize(x, FMT8, use_pallas=False)
    q_new = ops.f2p_quantize(x, FMT8, backend="xla")
    np.testing.assert_array_equal(np.asarray(q_legacy.codes),
                                  np.asarray(q_new.codes))
    with pytest.raises(ValueError, match="not both"):
        ops.f2p_quantize(x, FMT8, backend="xla", use_pallas=True)


# ---------------------------------------------------------------------------
# backends agree bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [FMT8, FMT16], ids=str)
def test_xla_and_pallas_interpret_agree(fmt):
    x = _data()
    qx = ops.f2p_quantize(x, fmt, backend="xla")
    qp = ops.f2p_quantize(x, fmt, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(qx.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(qx.scales), np.asarray(qp.scales))
    np.testing.assert_array_equal(np.asarray(qx.dequantize(backend="xla")),
                                  np.asarray(qx.dequantize(
                                      backend="pallas_interpret")))


def test_dequant_matmul_backends_agree():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    codes, scales = FM.quantize_weight(w)
    y_xla = FM.dequant_matmul(x, codes, scales, backend="xla")
    y_int = FM.dequant_matmul(x, codes, scales, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_int),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# LUT decode variant (xla backend, 8-bit formats)
# ---------------------------------------------------------------------------
LUT_FMTS = [F2PFormat(8, h, fl, signed)
            for h, fl, signed in itertools.product(
                (1, 2), Flavor, (False, True))] + \
           [F2PFormat(6, 2, Flavor.SR, signed=True)]


@pytest.mark.parametrize("fmt", LUT_FMTS, ids=str)
def test_lut_decode_bit_identical_all_codes(fmt):
    codes = jnp.arange(1 << fmt.n_bits, dtype=jnp.uint8)
    np.testing.assert_array_equal(
        np.asarray(K.dequantize_lut(codes, fmt)),
        np.asarray(K.dequantize_tile_math(codes, fmt)), err_msg=str(fmt))


def test_xla_dequantize_uses_lut_transparently():
    """8-bit xla dequantize (LUT inside) == interpret-Pallas (bit math)."""
    x = _data(seed=5)
    qt = ops.f2p_quantize(x, FMT8, backend="xla")
    np.testing.assert_array_equal(
        np.asarray(qt.dequantize(backend="xla")),
        np.asarray(qt.dequantize(backend="pallas_interpret")))
