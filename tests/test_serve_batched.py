"""Continuous-batching engine over the block-paged packed-F2P KV pool
(serve/batched.py + serve/paging.py, DESIGN.md §12).

Pins the ISSUE-8 acceptance bar: per-request greedy outputs from the batched
engine are BITWISE-identical to the sequential engine on mixed-length,
staggered-arrival workloads; page relocation and compaction are bit-exact on
the decode output across n_bits {6, 8, 16} on BOTH the xla and
pallas_interpret backends; preempt -> evict-to-host -> readmit is greedy-
identical to an uninterrupted run; temperature sampling is a pure function
of (seed, request id, position) so co-scheduling can never perturb a
request's draws; the sequential engine pads partial batches and syncs EOS
only periodically; and the pool reports word-granular packed bytes through
the canonical ``packed_nbytes`` accounting.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.autotune.policy import FormatPolicy, PolicyRule
from repro.configs import smoke_config
from repro.core.qtensor import QTensor
from repro.models import init_params
from repro.serve import (BatchedEngine, BatchedServeConfig, Engine,
                         PagedKVPool, PoolExhausted, Request, ServeConfig)
from repro.serve.arch import arch_for


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("llama3_2_3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n, seed=3, lmax=13, max_new=8, stagger=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, lmax))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(4, max_new + 1)),
                    arrival=stagger * u)
            for u in range(n)]


def _sequential(cfg, params, reqs, max_seq, **scfg_kw):
    eng = Engine(cfg, ServeConfig(batch=1, max_seq=max_seq,
                                  quantized_kv=True, packed_kv=True,
                                  fused_attention=True, **scfg_kw), params)
    return {r.uid: np.asarray(eng.generate(r.tokens[None], r.max_new)[0],
                              np.int32)
            for r in reqs}


def test_batched_matches_sequential_mixed_lengths(setup):
    """The tentpole contract: dynamic admission into fixed decode slots,
    ragged prompts through bucketed prefill, join-on-decode — and every
    request's greedy tokens still bitwise equal a solo sequential run."""
    cfg, params = setup
    reqs = _requests(cfg, 8, stagger=2)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=4, max_seq=32), params)
    out = eng.run(reqs)
    seq = _sequential(cfg, params, reqs, 32)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])
    assert eng.stats["prefills"] == len(reqs)
    # all request pages reclaimed; only the engine's reserved dump page
    # (paged decode) stays allocated for its lifetime
    assert eng.stats["pool"]["used"] == eng.stats.get("reserved_pages", 0)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("nbits,fmt", [(6, "f2p_sr_2_6s"),
                                       (8, "f2p_sr_2_8s"),
                                       (16, "f2p_lr_2_16s")])
def test_page_relocation_bitwise_on_decode(setup, monkeypatch, backend,
                                           nbits, fmt):
    """Relocating (and compacting) a request's pages between prefill-store
    and slot-load must not flip a single decode token: pages move as whole
    uint32 words (block = head_dim), never repacked. Pinned across n_bits
    and on both kernel backends. Runs the copy-in engine, where pages are a
    transit store and a single-table compact is safe (the paged engine's
    in-place defrag is pinned by test_paged_defrag_compact_mid_decode)."""
    cfg, params = setup
    monkeypatch.setenv("F2P_BACKEND", backend)
    pol = FormatPolicy(rules=(PolicyRule("kv/*", fmt, 0),))
    reqs = _requests(cfg, 3, seed=nbits, max_new=6)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                kv_policy=pol,
                                                paged_decode=False), params)
    store = eng.pool.store_prefill

    def store_then_relocate(caches, length, row=0):
        table = store(caches, length, row)
        table = eng.pool.relocate(table)       # alloc-copy-free to new pages
        eng.pool.compact([table])              # then defrag to the bottom
        return table

    eng.pool.store_prefill = store_then_relocate
    out = eng.run(reqs)

    ref = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                kv_policy=pol), params)
    want = ref.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], want[r.uid])


def test_preempt_evict_readmit_bitwise(setup):
    """Starvation preempts the longest-tail slot, pages out its KV to host
    numpy, and readmits it later — the resumed request's tokens must be
    bitwise-identical to an uninterrupted sequential run."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    # uniformly long decodes: no slot retires for several rounds, so the
    # waiting requests genuinely starve and the preemption path fires
    reqs = [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 13))
                                        ).astype(np.int32),
                    max_new=16)
            for u in range(5)]
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                sync_every=4,
                                                preempt_patience=1), params)
    out = eng.run(reqs)
    assert eng.stats.get("preemptions", 0) > 0
    assert eng.stats.get("host_evictions", 0) > 0
    assert eng.stats.get("readmits", 0) > 0
    seq = _sequential(cfg, params, reqs, 32)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])


def test_pool_relocate_compact_words_bitexact(setup):
    """Pool-level pin: after relocate + compact, the evicted word images
    (codes AND scales) are byte-identical to the original store."""
    cfg, params = setup
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32), params)
    pool = eng.pool
    tok0, pf, L = eng._prefill_request(np.arange(11, dtype=np.int32) % 50)
    t1 = pool.store_prefill(pf, L)
    t2 = pool.store_prefill(pf, L)
    t2 = pool.relocate(t2)
    pool.free(t1.pages)
    pool.compact([t2])
    assert t2.pages == list(range(len(t2.pages)))   # defragged to the bottom
    a = pool.evict_to_host(t2)
    t3 = pool.restore_from_host(a)
    b = pool.evict_to_host(t3)
    for key in a.data:
        for kv in ("k", "v"):
            np.testing.assert_array_equal(a.data[key][kv][0],
                                          b.data[key][kv][0])
            np.testing.assert_array_equal(a.data[key][kv][1],
                                          b.data[key][kv][1])


def test_sampling_pure_function_of_request_and_position(setup):
    """Temperature draws fold (seed, request uid, position) — which other
    requests share the batch, and which slot a request lands in, can never
    perturb its sampled tokens."""
    cfg, params = setup
    bs = dict(slots=3, max_seq=32, temperature=0.8, seed=5)
    target = Request(uid=41, tokens=np.arange(7, dtype=np.int32), max_new=8)
    alone = BatchedEngine(cfg, BatchedServeConfig(**bs), params).run([target])
    crowd = _requests(cfg, 4, seed=9, max_new=8)
    co = BatchedEngine(cfg, BatchedServeConfig(**bs), params).run(
        crowd + [target])
    np.testing.assert_array_equal(alone[41], co[41])


def test_sequential_engine_partial_batch_padding(setup):
    """B < configured batch pads to the compiled shape and slices the pad
    rows off — bitwise equal to the same rows in a full batch (and no
    recompile / hard assert)."""
    cfg, params = setup
    eng = Engine(cfg, ServeConfig(batch=4, max_seq=32, quantized_kv=True,
                                  packed_kv=True, fused_attention=True),
                 params)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (4, 9)).astype(np.int32)
    full = eng.generate(prompts, 6)
    part = eng.generate(prompts[:2], 6)
    assert part.shape == (2, 6)
    np.testing.assert_array_equal(part, full[:2])
    with pytest.raises(ValueError):
        eng.generate(rng.integers(0, cfg.vocab_size, (5, 9)), 4)


def test_sequential_engine_eos_periodic_sync(setup):
    """EOS mode syncs the device-side done flag every eos_sync_every steps
    instead of per token; rows keep their exact pre-EOS token stream and the
    loop still stops early once every row is done."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32)
    free = Engine(cfg, ServeConfig(batch=2, max_seq=64, quantized_kv=True,
                                   packed_kv=True, fused_attention=True),
                  params).generate(prompts, 40)
    eos = int(free[0, 5])                  # a token row 0 really emits
    eng = Engine(cfg, ServeConfig(batch=2, max_seq=64, quantized_kv=True,
                                  packed_kv=True, fused_attention=True,
                                  eos_sync_every=4), params)
    got = eng.generate(prompts, 40, eos=eos)
    # the generated stream is a prefix of the unconstrained run
    np.testing.assert_array_equal(got, free[:, :got.shape[1]])
    if all((free[b] == eos).any() for b in range(2)):
        # every row hit eos -> the loop stops early, overrunning the last
        # row's EOS by at most eos_sync_every - 1 tokens
        last = max(int(np.argmax(free[b] == eos)) for b in range(2))
        assert got.shape[1] <= last + 1 + 3


def test_architecture_registry():
    """arch_for classifies every family and resolves per-config capability:
    MoE capacity dropping breaks exact co-batching; attention-free xLSTM
    gets no paged pool; mamba hybrids get exact-length prefill."""
    lla = arch_for(smoke_config("llama3_2_3b"))
    assert (lla.name, lla.paged_kv, lla.recurrent_state,
            lla.exact_cobatch) == ("llama-dense", True, False, True)
    moe = arch_for(smoke_config("llama4_scout_17b"))
    assert moe.name == "moe" and moe.paged_kv and not moe.exact_cobatch
    ssm = arch_for(smoke_config("jamba_1_5_large"))
    assert ssm.name == "ssm-hybrid" and ssm.recurrent_state
    assert ssm.prefill_buckets == ()       # exact-length prefill
    xl = arch_for(smoke_config("xlstm_125m"))
    assert xl.name == "xlstm" and not xl.paged_kv and xl.recurrent_state


def test_recurrent_family_through_batched_engine():
    """A mamba-hybrid config runs the full admit/decode/harvest loop with
    per-slot recurrent state and exact-length prefill, bitwise equal to the
    sequential engine."""
    cfg = smoke_config("jamba_1_5_large")
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests(cfg, 3, seed=2, max_new=6)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32), params)
    out = eng.run(reqs)
    seq = _sequential(cfg, params, reqs, 32)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])


def test_pool_accounting_word_granular(setup):
    """Pool byte reports go through the canonical packed_nbytes (QTensor
    .nbytes): word-granular packed bytes, a whole-pool logical-f32
    comparison, and page_bytes * n_pages == pool_bytes."""
    cfg, _ = setup
    pool = PagedKVPool(cfg, 8, 16)
    from repro.kernels.bits import packed_nbytes
    want = 0
    for key in pool.attn_keys:
        for kv in ("k", "v"):
            qt = pool.slabs[key][kv]
            assert isinstance(qt, QTensor) and qt.packed
            n = int(np.prod(qt.shape[:-1]))
            want += packed_nbytes(qt.shape[-1], qt.fmt.n_bits) * n \
                + qt.scales.size * 4
    s = pool.stats()
    assert s["pool_bytes_packed"] == want
    assert s["page_bytes_packed"] * pool.n_pages == s["pool_bytes_packed"]
    assert s["pool_bytes_logical_f32"] > s["pool_bytes_packed"]


def test_pool_exhaustion_and_free_validation(setup):
    cfg, _ = setup
    pool = PagedKVPool(cfg, 8, 4)
    pages = pool.alloc(3)
    with pytest.raises(PoolExhausted):
        pool.alloc(2)
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free(pages)                   # double free
    with pytest.raises(ValueError):
        pool.free([99])                    # out of range


def test_admission_rejects_oversized_request(setup):
    cfg, params = setup
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32), params)
    bad = Request(uid=1, tokens=np.zeros(20, np.int32), max_new=20)
    with pytest.raises(ValueError):
        eng.run([bad])


# ---------------------------------------------------------------------------
# ISSUE 10: paged decode attends the page tables in place
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("nbits,fmt", [(6, "f2p_sr_2_6s"),
                                       (8, "f2p_sr_2_8s"),
                                       (16, "f2p_lr_2_16s")])
def test_paged_vs_copy_in_engine_bitwise(setup, monkeypatch, backend, nbits,
                                         fmt):
    """The ISSUE-10 acceptance bar: the paged engine (slots hold only a
    PageTable, the kernel attends pool slabs through it) emits bitwise the
    same greedy tokens as the copy-in engine (pages word-copied into a dense
    slot row) — across n_bits {6, 8, 16} on both kernel backends, with
    staggered arrivals exercising join-on-decode, growth, and release."""
    cfg, params = setup
    monkeypatch.setenv("F2P_BACKEND", backend)
    pol = FormatPolicy(rules=(PolicyRule("kv/*", fmt, 0),))
    reqs = _requests(cfg, 6, seed=nbits + 20, stagger=3)
    base = dict(slots=3, max_seq=32, kv_policy=pol, sync_every=4)
    paged = BatchedEngine(cfg, BatchedServeConfig(**base), params)
    assert paged.paged
    copyin = BatchedEngine(
        cfg, BatchedServeConfig(paged_decode=False, **base), params)
    assert not copyin.paged
    a, b = paged.run(reqs), copyin.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(a[r.uid], b[r.uid])
    assert paged.stats["pool"]["used"] == 1        # only the dump page


def test_paged_defrag_compact_mid_decode(setup):
    """Pool defrag under live decode: every round, relocate one live slot's
    pages AND compact the whole pool (dump page first, live tables, parked
    tables). Whole-word moves must not flip one emitted token."""
    cfg, params = setup
    reqs = _requests(cfg, 5, seed=31, stagger=2, max_new=10)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                sync_every=4,
                                                defrag_every=1), params)
    compacts = 0
    orig = eng.compact_pool

    def chaos_compact():
        nonlocal compacts
        live = [s for s, t in enumerate(eng._tables) if t is not None]
        if live:
            eng.relocate_slot(live[compacts % len(live)])
        orig()
        compacts += 1

    eng.compact_pool = chaos_compact
    out = eng.run(reqs)
    assert compacts > 2
    ref = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                sync_every=4), params)
    want = ref.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], want[r.uid])
    assert eng.stats["pool"]["used"] == 1


def test_paged_preempt_evict_readmit_bitwise(setup):
    """Paged park hands the PageTable itself over (trim -> evict-to-host);
    readmission adopts restored pages — no dense row anywhere. Tokens stay
    bitwise equal to the sequential engine through the round trip."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    reqs = [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 13))
                                        ).astype(np.int32),
                    max_new=16)
            for u in range(5)]
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                sync_every=4,
                                                preempt_patience=1), params)
    assert eng.paged
    out = eng.run(reqs)
    assert eng.stats.get("preemptions", 0) > 0
    assert eng.stats.get("host_evictions", 0) > 0
    assert eng.stats.get("readmits", 0) > 0
    assert eng.stats["pool"]["used"] == 1
    seq = _sequential(cfg, params, reqs, 32)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])


def test_io_upload_delta_vs_full_bitwise(setup):
    """The delta-masked boundary upload (only dirty slots overwrite the
    device vectors) is bitwise-invisible vs re-uploading the full host
    mirrors every dirty round."""
    cfg, params = setup
    reqs = _requests(cfg, 6, seed=23, stagger=3)
    outs = {}
    for mode in ("delta", "full"):
        eng = BatchedEngine(cfg, BatchedServeConfig(slots=3, max_seq=32,
                                                    sync_every=4,
                                                    io_upload=mode), params)
        outs[mode] = eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs["delta"][r.uid],
                                      outs["full"][r.uid])


def test_slo_scheduler_matches_fifo_outputs_and_bounds_starvation(setup):
    """Latency-aware admission reorders WHICH request gets a free slot, but
    per-request outputs are a pure function of the request (exact_cobatch),
    so every request must still emit its sequential tokens — and the
    preempt_patience hard floor guarantees nothing starves forever even
    with the tail-penalty scoring active."""
    cfg, params = setup
    rng = np.random.default_rng(29)
    # heavy pressure: 8 requests with mixed tails onto 2 slots, all visible
    # at once so the scorer (not arrival order) decides admission
    reqs = [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 13))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(4, 16)))
            for u in range(8)]
    outs = {}
    for sched in ("slo", "fifo"):
        eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                    sync_every=4,
                                                    scheduler=sched), params)
        outs[sched] = eng.run(reqs)
        assert len(outs[sched]) == len(reqs)       # nothing starved
    seq = _sequential(cfg, params, reqs, 32)
    for r in reqs:
        np.testing.assert_array_equal(outs["slo"][r.uid], seq[r.uid])
        np.testing.assert_array_equal(outs["fifo"][r.uid], seq[r.uid])


def test_paged_pool_bytes_page_granular(setup):
    """With paged decode there is no [slots, max_seq] dense KV mirror: the
    resident KV footprint is pool_bytes_live_packed — allocated pages only,
    scaling with live tokens at page granularity."""
    cfg, params = setup
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=4, max_seq=32), params)
    assert eng.paged
    # decode caches hold the pool slabs themselves, not per-slot dense rows
    for key in eng.pool.attn_keys:
        for kv in ("k", "v"):
            assert eng.caches[key][kv] is eng.pool.slabs[key][kv]
    s = eng.pool.stats()
    assert s["pool_bytes_live_packed"] == s["used"] * s["page_bytes_packed"]
    assert s["used"] == 1                          # just the dump page idle
    reqs = _requests(cfg, 2, seed=5, max_new=4)
    eng.run(reqs)
    assert eng.pool.stats()["used"] == 1           # all request pages freed


@pytest.mark.parametrize("fmt,order", [("f2p_sr_2_8s", "planes"),
                                       ("f2p_sr_2_6s", "per_head")])
def test_engine_records_attention_decode_order(setup, fmt, order):
    """stats["attn_decode_order"] says which decode served: the benchmark
    cell's MHA rows over its f2p_sr_2_8s pool (the default KV format) take
    the storage-order planes; 6-bit fields straddle words and keep the
    per-head unpack."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)    # MHA, G=1
    params = init_params(cfg, jax.random.PRNGKey(0))
    pol = FormatPolicy(rules=(PolicyRule("kv/*", fmt, 0),))
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                kv_policy=pol), params)
    out = eng.run(_requests(cfg, 2, max_new=4))
    assert all(len(t) for t in out.values())
    assert eng.stats["attn_decode_order"] == order
