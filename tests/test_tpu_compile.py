"""Every Pallas kernel compiles for a TPU v5e at llama-3.2-3B decode widths.

Interpret mode accepts block shapes and in-kernel reshapes that the TPU
compiler (Mosaic) refuses, so the CPU parity tests cannot see these
failures. The compiles here target a described ``v5e:2x2`` topology — no
chip is attached — and check that the compiled HLO calls the kernel
(``tpu_custom_call``). The topology is described inside a module fixture,
never at import: only one process may load the TPU library.

Widths: B=32 decode slots, K=8 kv heads, G=3 query heads per kv head,
head_dim 128, 8-bit packed F2P KV in 8-token pages, 2048-token span
(tile 128); quantize/matmul operands are d_model=3072, d_ff=8192.
``attention_paged_mha`` compiles the paged kernel again at the benchmark
cell's widths (Phi-3-mini: B=16, K=32 kv heads of 96, G=1), where the
storage-order planes are 768 lanes of 32 heads.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qtensor as QT
from repro.core.f2p import F2PFormat, Flavor
from repro.core.qtensor import QTensor
from repro.kernels import f2p_attention as FA
from repro.kernels import f2p_counter as FC
from repro.kernels import f2p_matmul as FM
from repro.kernels import f2p_quant as FQ
from repro.kernels.bits import packed_words

FMT8 = F2PFormat(8, 2, Flavor.SR, signed=True)
FMT6 = F2PFormat(6, 2, Flavor.SR, signed=True)
B, K, G, HD, T, SPAN = 32, 8, 3, 128, 8, 2048
D, FF = 3072, 8192
W8 = packed_words(HD, 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache (no chip to load them): keep it off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _paged(b, k, g, hd):
    """attention_paged over the engine's default pool size for ``b`` slots of
    ``k`` kv heads of ``hd`` (``g`` query heads each): (fn, arg shapes)."""
    w, p = packed_words(hd, 8), (b + 1) * SPAN // T + 1

    def fn(q, kc, ks, vc, vs, pages, kv_len):
        kq = QTensor.from_parts(kc, ks, FMT8, hd, (p, T, k * hd), packed=True)
        vq = QTensor.from_parts(vc, vs, FMT8, hd, (p, T, k * hd), packed=True)
        return FA.attention_paged(q, kq, vq, pages, kv_len=kv_len,
                                  backend="pallas")

    return fn, [((b, 1, k * g, hd), jnp.bfloat16),
                ((p, T, k * w), jnp.uint32), ((p, T, k), jnp.float32),
                ((p, T, k * w), jnp.uint32), ((p, T, k), jnp.float32),
                ((b, SPAN // T), jnp.int32), ((b,), jnp.int32)]


def _packed(q, kc, ks, vc, vs, kv_len):
    kq = QTensor.from_parts(kc, ks, FMT8, HD, (B, SPAN, K, HD), packed=True)
    vq = QTensor.from_parts(vc, vs, FMT8, HD, (B, SPAN, K, HD), packed=True)
    return FA.attention_packed(q, kq, vq, kv_len=kv_len, backend="pallas")


def _kv_write(x):
    return QT.quantize(x, FMT8, block=HD, packed=True, backend="pallas").codes


def _advance(st, budget, u, p, run, logq):
    return FC._advance_pallas_jit(st, budget, u, p, run, logq,
                                  sweeps=FC.PALLAS_SWEEPS, kmax=63,
                                  interpret=False)


i32, u32, f32, bf16 = jnp.int32, jnp.uint32, jnp.float32, jnp.bfloat16
CASES = {
    # the serving decode round's kernels
    "attention_paged": _paged(B, K, G, HD),
    "attention_paged_mha": _paged(16, 32, 1, 96),
    "quantize_packed_kv_write": (_kv_write, [((B, 1, K, HD), f32)]),
    # the copy-in path and the packed weight / tensor codecs
    "attention_packed": (_packed, [
        ((B, 1, K * G, HD), bf16), ((B, SPAN, K, W8), u32),
        ((B, SPAN, K, 1), f32), ((B, SPAN, K, W8), u32),
        ((B, SPAN, K, 1), f32), ((B,), i32)]),
    "quantize_packed": (
        lambda x: FQ.f2p_quantize_packed_pallas(x, FMT8, interpret=False),
        [((D, FF), f32)]),
    "dequantize_packed": (
        lambda w, s: FQ.f2p_dequantize_packed_pallas(w, s, FMT8,
                                                     interpret=False),
        [((D, FF // 4), u32), ((D, FF // 128), f32)]),
    "quantize": (lambda x: FQ.f2p_quantize_pallas(x, FMT8, interpret=False),
                 [((D, FF), f32)]),
    "dequant_matmul_packed": (
        lambda x, w, s: FM.f2p_dequant_matmul_packed(x, w, s, fmt=FMT8,
                                                     interpret=False),
        [((B, D), bf16), ((D, FF // 4), u32), ((D // 128, FF), f32)]),
    "dequant_matmul_packed_6bit": (
        lambda x, w, s: FM.f2p_dequant_matmul_packed(x, w, s, fmt=FMT6,
                                                     interpret=False),
        [((B, D), bf16), ((D, FF * 6 // 32), u32), ((D // 128, FF), f32)]),
    "dequant_matmul": (
        lambda x, c, s: FM.f2p_dequant_matmul(x, c, s, fmt=FMT8,
                                              interpret=False),
        [((B, D), bf16), ((D, FF), jnp.uint8), ((D // 128, FF), f32)]),
    "counter_advance": (_advance, [
        ((4, 4096), i32), ((4, 4096), f32),
        ((FC.PALLAS_SWEEPS, 4, 4096), f32), ((64,), f32), ((64,), f32),
        ((64,), f32)]),
}


def _compile(fn, shapes, one_chip) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    assert "tpu_custom_call" in _compile(*CASES[name], one_chip), name


@pytest.mark.parametrize("name", ["quantize_packed_kv_write",
                                  "attention_paged", "attention_paged_mha"])
def test_kernel_compiles_under_highest_matmul_precision(one_chip, name):
    """An ambient ``default_matmul_precision("highest")`` must not reach the
    kernels' bf16 lane-move matmuls (Mosaic refuses f32 contraction of
    bf16 operands); the attention kernels' f32 selection matmuls pin
    HIGHEST themselves."""
    with jax.default_matmul_precision("highest"):
        assert "tpu_custom_call" in _compile(*CASES[name], one_chip), name
