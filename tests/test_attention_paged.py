"""Paged fused attention (kernels/f2p_attention.attention_paged,
DESIGN.md §14).

Pins the ISSUE-10 tentpole contract: attending THROUGH a page table is
BITWISE-identical to gathering the pages into a dense row and running
``attention_packed`` on it — across formats x n_bits in {6, 8, 16}, on both
the xla and pallas_interpret backends, with odd page counts, partially
filled last pages, and garbage page ids beyond ``kv_len`` contributing
exactly 0.0; the tile loop must span whole pages (tile % page_tokens == 0
is enforced); and the model layer (``decode_step`` with ``pages``) produces
bitwise the same logits as the dense copy-in decode path.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import qtensor as QT
from repro.core.f2p import F2PFormat, Flavor
from repro.kernels import f2p_attention as FA

FORMATS = [F2PFormat(6, 2, Flavor.SR, signed=True),
           F2PFormat(8, 2, Flavor.SR, signed=True),
           F2PFormat(16, 2, Flavor.LR, signed=True)]


def _slab(seed, P=11, T=8, K=2, hd=32, fmt=FORMATS[1]):
    """A pool slab of random KV: packed QTensor [P, T, K*hd] blocked over
    hd (codes [P, T, K*words], scales [P, T, K])."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(P, T, K * hd)).astype(np.float32))
    return QT.quantize(x, fmt, block=hd, packed=True, backend="xla")


def _case(seed, B=3, P=11, maxp=5, T=8, K=2, G=2, hd=32, fmt=FORMATS[1],
          Sq=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Sq, K * G, hd)).astype(np.float32))
    kq = _slab(seed + 1, P=P, T=T, K=K, hd=hd, fmt=fmt)
    vq = _slab(seed + 2, P=P, T=T, K=K, hd=hd, fmt=fmt)
    pages = rng.integers(0, P, size=(B, maxp)).astype(np.int32)
    return q, kq, vq, jnp.asarray(pages)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("fmt", FORMATS, ids=lambda f: f"n{f.n_bits}")
def test_paged_bitwise_vs_gather_to_dense(fmt, backend):
    """The tentpole pin: per-row page indirection + in-register superblock
    decode == dense gather + attention_packed, bit for bit. maxp=5 is an
    odd page count (a ragged last tile at tile=16) and the per-row kv_len
    values leave partially filled last pages."""
    q, kq, vq, pages = _case(0, fmt=fmt)
    kv_len = jnp.asarray([33, 40, 7], jnp.int32)   # partial / full / 1 page
    for tile in (8, 16, 40):
        ref = FA.attention_paged_reference(q, kq, vq, pages, kv_len=kv_len,
                                           tile=tile)
        got = FA.attention_paged(q, kq, vq, pages, kv_len=kv_len,
                                 backend=backend, tile=tile)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("fmt", FORMATS, ids=lambda f: f"n{f.n_bits}")
def test_paged_backends_agree_bitwise(fmt):
    q, kq, vq, pages = _case(1, maxp=3, fmt=fmt)
    kv_len = jnp.asarray([20, 24, 3], jnp.int32)
    a = FA.attention_paged(q, kq, vq, pages, kv_len=kv_len, backend="xla",
                           tile=8)
    b = FA.attention_paged(q, kq, vq, pages, kv_len=kv_len,
                           backend="pallas_interpret", tile=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_garbage_pages_beyond_kv_len_contribute_zero(backend):
    """Positions >= kv_len — including WHOLE pages whose table entries are
    unassigned garbage ids — must contribute exactly 0.0: the mask sets
    their scores to -inf before exp, so any finite decoded value is
    annihilated. Scrambling every page the row does not live in must not
    flip one bit of the output."""
    fmt = FORMATS[1]
    q, kq, vq, pages = _case(2, B=2, P=8, maxp=4)  # live ids only in 0..7
    kq = QT.QTensor.from_parts(          # widen the slabs by a 9th page (id
        jnp.pad(kq.codes, ((0, 1),) + ((0, 0),) * 2),   # 8) no row lives in
        jnp.pad(kq.scales, ((0, 1),) + ((0, 0),) * 2),
        kq.fmt, kq.block, (9,) + tuple(kq.shape[1:]), packed=True)
    vq = QT.QTensor.from_parts(
        jnp.pad(vq.codes, ((0, 1),) + ((0, 0),) * 2),
        jnp.pad(vq.scales, ((0, 1),) + ((0, 0),) * 2),
        vq.fmt, vq.block, (9,) + tuple(vq.shape[1:]), packed=True)
    kv_len = jnp.asarray([19, 9], jnp.int32)       # rows live in pages 0..2
    base = FA.attention_paged(q, kq, vq, pages, kv_len=kv_len,
                              backend=backend, tile=16)
    # point every dead table entry at a "garbage" page filled with huge
    # values, and scramble the dead pages' codes too
    live = -(-np.asarray(kv_len)[:, None] // 8)    # pages_for per row
    pg = np.asarray(pages).copy()
    dead_mask = np.arange(pg.shape[1])[None, :] >= live
    pg[dead_mask] = 8                              # the garbage page id
    big = jnp.full((1, 8, 2 * 32), 1e9, jnp.float32)
    bigq = QT.quantize(big, fmt, block=32, packed=True, backend="xla")
    kq2 = QT.QTensor.from_parts(
        kq.codes.at[8].set(bigq.codes[0]), kq.scales.at[8].set(bigq.scales[0]),
        kq.fmt, kq.block, kq.shape, packed=True)
    vq2 = QT.QTensor.from_parts(
        vq.codes.at[8].set(bigq.codes[0]), vq.scales.at[8].set(bigq.scales[0]),
        vq.fmt, vq.block, vq.shape, packed=True)
    got = FA.attention_paged(kq=kq2, vq=vq2, q=q, pages=jnp.asarray(pg),
                             kv_len=kv_len, backend=backend, tile=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_paged_tile_must_span_whole_pages():
    q, kq, vq, pages = _case(3)
    with pytest.raises(ValueError):
        FA.attention_paged(q, kq, vq, pages, kv_len=10, tile=12)  # 12 % 8


def test_gather_pages_to_dense_is_pure_word_copy():
    """gather_pages_to_dense never repacks: every output word is the exact
    uint32 of its source page."""
    kq = _slab(4)
    pages = jnp.asarray([[3, 0, 7], [1, 1, 10]], jnp.int32)
    dense = FA.gather_pages_to_dense(kq, pages)
    assert dense.codes.shape[:2] == (2, 24)
    for b in range(2):
        for j, p in enumerate(np.asarray(pages)[b]):
            np.testing.assert_array_equal(
                np.asarray(dense.codes[b, j * 8:(j + 1) * 8]).reshape(8, -1),
                np.asarray(kq.codes[p]))
            np.testing.assert_array_equal(
                np.asarray(dense.scales[b, j * 8:(j + 1) * 8]).reshape(8, -1),
                np.asarray(kq.scales[p]))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_model_decode_step_paged_logits_bitwise(backend, monkeypatch):
    """decode_step with a page table over pool slabs == decode_step over the
    dense copy-in cache, bitwise at the LOGITS level (not just argmax)."""
    monkeypatch.setenv("F2P_BACKEND", backend)
    from repro.configs import smoke_config
    from repro.models import decode_step, init_caches, init_params
    from repro.serve.paging import PagedKVPool

    cfg = smoke_config("llama3_2_3b")
    import dataclasses as dc
    cfg = dc.replace(cfg, fused_attention=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, T = 2, 32, 8
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab_size, (B, 9)).astype(np.int32)

    # dense copy-in path: prefill into a [B, S] cache, then decode
    from repro.models import prefill
    dense = init_caches(cfg, B, S, quantized_kv=True, packed_kv=True)
    logits0, dense = prefill(params, {"tokens": jnp.asarray(prompts)}, cfg,
                             dense)
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), 9, jnp.int32)

    # paged path: store each row's prefill KV into pool pages, adopt tables
    pool = PagedKVPool(cfg, T, 16)
    pf = init_caches(cfg, B, 16, quantized_kv=True, packed_kv=True)
    _, pf = prefill(params, {"tokens": jnp.asarray(
        np.pad(prompts, ((0, 0), (0, 7))))}, cfg, pf)
    tables = [pool.store_prefill(pf, 9, row=b) for b in range(B)]
    pages_h = np.zeros((B, S // T), np.int32)
    for b, t in enumerate(tables):
        pages_h[b, :len(t.pages)] = t.pages
    pages = jnp.asarray(pages_h)
    paged = {key: dict(pool.slabs[key]) for key in pool.attn_keys}

    for step in range(4):
        ld, dense = decode_step(params, tok, pos, dense, cfg)
        lp, paged = decode_step(params, tok, pos, paged, cfg, pages=pages)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        tok = jnp.argmax(ld, -1).astype(jnp.int32)[:, None]
        pos = pos + 1


# ---------------------------------------------------------------------------
# Storage-order ("planes") decode: formats whose fields never straddle a
# word decode where they lie, with the field permutation moved onto q and
# the output (kernels/f2p_attention.py module docstring, DESIGN.md §11.2).
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("K,G,hd", [(32, 1, 96), (8, 4, 128)],
                         ids=["mha_k32_hd96", "gqa_k8_g4_hd128"])
def test_paged_bitwise_vs_gather_to_dense_at_serving_widths(K, G, hd,
                                                            backend):
    """The benchmark cell's MHA widths (32 kv heads of 96: 768 lanes of
    words) and a GQA group of 4 at head_dim 128, 8-token pages, tile 128:
    paged == dense gather + attention_packed, bit for bit, over two tiles
    with rows ending mid-tile, mid-page and on a full span."""
    fmt = FORMATS[1]
    q, kq, vq, pages = _case(7, B=3, P=70, maxp=32, K=K, G=G, hd=hd,
                             fmt=fmt)
    kv_len = jnp.asarray([131, 256, 5], jnp.int32)
    ref = FA.attention_paged_reference(q, kq, vq, pages, kv_len=kv_len,
                                       tile=128)
    got = FA.attention_paged(q, kq, vq, pages, kv_len=kv_len,
                             backend=backend, tile=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("n_bits,order", [(4, "planes"), (8, "planes"),
                                          (16, "planes"), (6, "per_head")])
def test_decode_order_follows_word_straddling(n_bits, order):
    """Storage order needs every field inside one word: 32 % n_bits == 0.
    6-bit fields straddle words and keep the per-head unpack."""
    h_bits = 1 if n_bits < 6 else 2      # 4 bits leave room for H=1 only
    assert FA.decode_order(F2PFormat(n_bits, h_bits, Flavor.SR,
                                     signed=True)) == order


@pytest.mark.parametrize("P", [2, 4, 8])
def test_plane_permutation_round_trips(P):
    """q into planes and the output back out are exact inverse
    permutations, and plane p, lane h*W + w holds element P*w + p of head
    h: the element that word w of head h stores in field p."""
    B, K, R, hd = 2, 3, 5, 8 * P
    x = jnp.asarray(np.random.default_rng(P).normal(size=(B, K, R, hd))
                    .astype(np.float32))
    planes = FA._q_planes(x, P)
    assert planes.shape == (B, P, R, K * hd // P)
    W = hd // P
    for p, h, w in [(0, 0, 0), (P - 1, K - 1, W - 1), (1, 2, 3 % W)]:
        np.testing.assert_array_equal(planes[:, p, :, h * W + w],
                                      x[:, h, :, P * w + p])
    np.testing.assert_array_equal(np.asarray(FA._o_unplanes(planes, K)),
                                  np.asarray(x))


@pytest.mark.parametrize("fmt", [F2PFormat(4, 1, Flavor.SR, signed=True),
                                 FORMATS[1], FORMATS[2]],
                         ids=lambda f: f"n{f.n_bits}")
def test_plane_decode_equals_decode_rows_bitwise(fmt):
    """The kernel body's plane decode (shift + mask per field, scales spread
    to their heads' lanes by an exact 0/1 matmul) gives every K/V value
    bitwise what the per-row decode gives, in storage order."""
    K, hd, T = 3, 32, 16
    kq = _slab(8, P=1, T=T, K=K, hd=hd, fmt=fmt)
    words, scales = kq.codes[0], kq.scales[0]       # [T, K*W], [T, K]
    W = words.shape[-1] // K
    P = 32 // fmt.n_bits
    _, _, expand, _ = FA._selectors(K, 1, W, W)
    got = FA._decode_planes(words, scales, fmt, jnp.asarray(expand))
    rows = FA._decode_rows(words.reshape(T, K, W), scales[..., None], fmt,
                           hd)                      # [T, K, hd]
    want = FA._to_planes(rows[None], P)[0]          # [P, T, K*W]
    assert len(got) == P
    for p in range(P):
        np.testing.assert_array_equal(np.asarray(got[p]),
                                      np.asarray(want[p]))
