"""The serving loop's phase spans, its exact work counters, and the table of
its XLA programs (serve/batched.py).

Phases: tracing on changes no output token; the leaf phases nest inside
``round`` / ``prefill`` / ``prefill_group`` and tile ``run()``. Counters:
``slots``' ``kv_live`` / ``kv_written`` / ``steps_kept`` equal counts
derived by hand from the requests and their outputs, and ``kv_attended``
the rows and span each round's attention was handed. Programs: each program
the engine and its pool dispatch lowers to an HLO module that ``PROGRAMS``
lists.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import (BatchedEngine, BatchedServeConfig, Request, batched,
                         paging)

ROUND_LEAVES = ("round.prep", "round.launch", "round.wait")
PREFILL_LEAVES = ("prefill.launch", "prefill.store", "prefill.wait")
LEAVES = ("schedule", "harvest") + ROUND_LEAVES + PREFILL_LEAVES


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("llama3_2_3b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n, *, seed=3, lmin=3, lmax=13, new=(4, 9)):
    rng = np.random.default_rng(seed)
    return [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lmin, lmax))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(*new)))
            for u in range(n)]


def _traced(eng, reqs):
    """(outputs, tracer, run() start and end on the tracer's clock)."""
    st = obs.enable(trace=True)
    try:
        t0 = st.tracer.now_us()
        out = eng.run(reqs)
        t1 = st.tracer.now_us()
    finally:
        obs.disable()
    return out, st.tracer, t0, t1


def _spans(tracer, names):
    return [(e["name"], e["ts"], e["ts"] + e["dur"]) for e in tracer.events
            if e["ph"] == "X" and e["tid"] == 0 and e["name"] in names]


def _covered(spans) -> float:
    tot, end = 0.0, float("-inf")
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        s = max(s, end)
        if e > s:
            tot += e - s
            end = e
    return tot


@pytest.mark.parametrize("group", [1, 4], ids=["batch1", "grouped"])
def test_tracing_on_off_bitwise_with_phase_names(setup, group):
    cfg, params = setup
    reqs = _requests(cfg, 6)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=3, max_seq=32,
                                                prefill_group=group), params)
    off = eng.run(reqs)
    on, tracer, _, _ = _traced(eng, reqs)
    for r in reqs:
        np.testing.assert_array_equal(off[r.uid], on[r.uid])
    names = {e["name"] for e in tracer.events if e["tid"] == 0}
    assert set(LEAVES) | {"round"} <= names
    assert ("prefill_group" if group > 1 else "prefill") in names
    assert "slots" in names


# the loop's work, engine and pool: every call runs inside a leaf phase
LOOP_WORK = ("_free_slots", "_readmit", "_select_admissions", "_admit",
             "_admit_batch", "_place", "_grow_tables", "_bind_slabs",
             "_upload_io", "_push_slabs", "_kept_work", "_harvest", "_retire",
             "compact_pool", "_park_slot")
POOL_WORK = ("store_prefill", "load_into_slot", "free")


def _record_calls(obj, names, calls):
    """Wrap ``obj``'s methods ``names``: each traced call appends (name,
    start, end) on the tracer's clock to ``calls``."""
    def wrap(name, fn):
        def timed(*args, **kw):
            st = obs.get()
            if st is None:
                return fn(*args, **kw)
            t0 = st.tracer.now_us()
            try:
                return fn(*args, **kw)
            finally:
                calls.append((name, t0, st.tracer.now_us()))
        return timed
    for n in names:
        setattr(obj, n, wrap(n, getattr(obj, n)))


@pytest.mark.parametrize("group", [1, 2], ids=["batch1", "grouped"])
def test_phases_nest_and_tile_run(setup, group):
    cfg, params = setup
    reqs = _requests(cfg, 16, new=(56, 65))
    # 64-step rounds: each phase boundary costs the host some microseconds
    # whatever a round holds, so a round as short as the 8-step default is
    # here (milliseconds; a served model's takes seconds) would measure that
    # cost and not whether every piece of the loop falls in a phase
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=4, max_seq=128,
                                                sync_every=64,
                                                prefill_group=group), params)
    eng.run(reqs)                           # compile every shape first
    calls = []
    _record_calls(eng, LOOP_WORK, calls)
    _record_calls(eng.pool, POOL_WORK, calls)
    covered = []
    for _ in range(5):
        calls.clear()                       # each job has its own clock
        _, tracer, t0, t1 = _traced(eng, reqs)
        covered.append(_covered(_spans(tracer, LEAVES)) / (t1 - t0))
    # the last job's spans: leaves nest in their parents
    parents = _spans(tracer, ("round", "prefill", "prefill_group"))
    for leaves, holders in ((ROUND_LEAVES, ("round",)),
                            (PREFILL_LEAVES, ("prefill", "prefill_group"))):
        for name, s, e in _spans(tracer, leaves):
            assert any(n in holders and ps <= s and e <= pe
                       for n, ps, pe in parents), (name, s, e)
    # admission prefills in schedule, a preemption's in harvest
    loop = _spans(tracer, ("schedule", "harvest"))
    for name, s, e in _spans(tracer, ("prefill", "prefill_group")):
        assert any(ps <= s and e <= pe for _, ps, pe in loop), name
    # the loop's phases follow one another, and every piece of the loop's
    # work runs inside a leaf
    top = _spans(tracer, ("schedule", "round", "harvest"))
    assert _covered(top) == pytest.approx(sum(e - s for _, s, e in top))
    leaves = _spans(tracer, LEAVES)
    assert {n for n, _, _ in calls} >= {"_select_admissions", "_harvest",
                                        "_upload_io", "store_prefill", "free"}
    for name, s, e in calls:
        assert any(ps <= s and e <= pe for _, ps, pe in leaves), (name, s, e)
    # what is left between the leaves is the loop's turn from one phase to
    # the next: the leaves cover run(), in the median of five jobs (a job on
    # a shared host can lose milliseconds anywhere, phases or not)
    assert sorted(covered)[2] >= 0.99, covered


def _counters(tracer, name):
    return [e["args"] for e in tracer.events
            if e["ph"] == "C" and e["name"] == name]


def _hand_kv(reqs, outs):
    """kv_live / kv_written of the kept decode tokens: token j >= 1 of a
    request with an L-token prompt comes from the step that attends L + j
    positions."""
    live = written = 0
    for r in reqs:
        L, n = len(r.tokens), len(outs[r.uid])
        written += n - 1
        live += sum(L + j for j in range(1, n))
    return live, written


@pytest.mark.parametrize("slots,group,eos", [(3, 1, False), (3, 4, False),
                                             (1, 1, False), (3, 1, True)],
                         ids=["batch1", "grouped", "one-slot", "eos"])
def test_counters_equal_hand_counts(setup, slots, group, eos):
    cfg, params = setup
    reqs = _requests(cfg, 6, new=(4, 19))
    bs = BatchedServeConfig(slots=slots, max_seq=48, prefill_group=group)
    eng = BatchedEngine(cfg, bs, params)
    if eos:
        # an EOS some answers reach early, so harvest cuts them short
        first = eng.run(reqs)
        tok = next(int(t) for r in reqs for t in first[r.uid][1:-1])
        bs = BatchedServeConfig(slots=slots, max_seq=48, eos=tok,
                                prefill_group=group)
        eng = BatchedEngine(cfg, bs, params)
    outs, tracer, _, _ = _traced(eng, reqs)
    if eos:
        assert any(len(outs[r.uid]) < r.max_new for r in reqs)
    slots_ev = _counters(tracer, "slots")
    live, written = _hand_kv(reqs, outs)
    assert sum(e["kv_written"] for e in slots_ev) == written
    assert sum(e["kv_live"] for e in slots_ev) == live
    for e in slots_ev:
        assert 0 < e["steps_kept"] <= bs.sync_every
        assert e["steps_kept"] <= e["kv_written"] <= (
            e["steps_kept"] * e["active"])
    if slots == 1:
        assert all(e["steps_kept"] == e["kv_written"] for e in slots_ev)


@pytest.mark.parametrize("slots,paged", [(3, True), (5, True), (3, False)],
                         ids=["paged", "idle-rows", "copy-in"])
def test_kv_attended_is_rows_times_span_per_kept_step(setup, slots, paged):
    """``kv_attended`` counts every slot row over the span each round's
    attention was handed (the page table's width, or max_seq for the dense
    copy-in row) for each kept step, so it bounds ``kv_live``."""
    cfg, params = setup
    reqs = _requests(cfg, 6, new=(20, 60))
    bs = BatchedServeConfig(slots=slots, max_seq=128, paged_decode=paged)
    eng = BatchedEngine(cfg, bs, params)
    spans = []
    launch = eng._round

    def round_spy(params, caches, tok, pos, req, pages):
        spans.append(bs.max_seq if pages is None
                     else pages.shape[1] * eng.page_tokens)
        return launch(params, caches, tok, pos, req, pages)

    eng._round = round_spy
    _, tracer, _, _ = _traced(eng, reqs)
    slots_ev = _counters(tracer, "slots")
    assert len(slots_ev) == len(spans)
    if paged:
        assert len(set(spans)) > 1          # the span bucket moved
    for e, span in zip(slots_ev, spans):
        assert e["kv_attended"] == slots * span * e["steps_kept"]
        assert e["kv_attended"] >= e["kv_live"]


def _hlo_module(lowered) -> str:
    head = lowered.as_text(dialect="hlo").split("\n", 1)[0]
    return head.split()[1].rstrip(",")


def _jitted(module):
    return {k: v for k, v in vars(module).items()
            if callable(v) and hasattr(v, "lower") and k.startswith("_")}


def test_every_engine_program_is_in_the_table(setup):
    cfg, params = setup
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32),
                        params)
    B, P = 2, eng.pages.shape[1]
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    mask = jnp.zeros((B,), bool)
    slab = {"a": jnp.zeros((2, 6, 4, 3), jnp.uint32)}
    cache = {"a": jnp.zeros((2, B, 8, 3), jnp.uint32)}
    pages, row = jnp.asarray([1, 2], jnp.int32), jnp.int32(0)
    bucket = eng.buckets[0]
    lowered = {
        "_round": eng._round.lower(params, eng.caches, eng.tok, eng.pos,
                                   eng.req, eng.pages),
        "_prefill": eng._prefill.lower(params, i32(1, bucket),
                                       eng._pf_template(1, bucket),
                                       i32(1)),
        "_io_delta": batched._io_delta.lower(i32(B, 1), i32(B), i32(B), mask,
                                             i32(B), i32(B), i32(B)),
        "_pages_delta": batched._pages_delta.lower(i32(B, P), mask,
                                                   i32(B, P)),
        "_leaf_set_slot": batched._leaf_set_slot.lower(
            jnp.zeros((2, B, 5)), jnp.zeros((2, 1, 5)), row),
        "_scatter_pages": paging._scatter_pages.lower(
            slab["a"], pages, jnp.zeros((2, 2, 4, 3), jnp.uint32)),
        "_gather_pages": paging._gather_pages.lower(slab["a"], pages),
        "_store_row_all": paging._store_row_all.lower(slab, cache, pages,
                                                      row),
        "_load_row_all": paging._load_row_all.lower(slab, cache, pages, row),
        "_move_pages_all": paging._move_pages_all.lower(slab, pages,
                                                        pages + 2),
    }
    # every module-level program of the engine and its pool is lowered here
    assert set(_jitted(batched)) | set(_jitted(paging)) <= set(lowered)
    names = {k: _hlo_module(lo) for k, lo in lowered.items()}
    assert all(n in batched.PROGRAMS for n in names.values()), names
    assert batched.PROGRAMS[names["_round"]] == "round"
    assert batched.PROGRAMS[names["_prefill"]] == "prefill"
    assert batched.PROGRAMS[names["_store_row_all"]] == "kv_store"
    assert set(batched.PROGRAMS.values()) == {"round", "prefill", "kv_store",
                                              "kv_move", "upload"}
