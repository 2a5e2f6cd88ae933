"""Observability layer (repro.obs, DESIGN.md §13).

Pins the ISSUE-9 acceptance bar: span nesting/ordering on the exported
timeline; Chrome trace_event JSON schema validity; F2P-histogram quantile
accuracy against an exact numpy oracle at n_bits {8, 16}; the disabled path
is a no-op (shared null context, zero events); engine outputs are
BITWISE-identical with tracing armed vs disarmed while ``engine.stats``
stays the exact-count compatibility view over the registry; and the
FL-fleet/sketch instrumentation exports the same numbers the drivers report.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.obs import ExpertLoadTracker, FlowStats, MetricsRegistry, SpanTracer


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with tracing disarmed (module-global)."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# metrics: counters / gauges / histograms over F2P cells
# ---------------------------------------------------------------------------
def test_counter_exact_shadow_and_estimate():
    reg = MetricsRegistry("t.counters", register=False)
    c = reg.counter("hits")
    for _ in range(100):
        c.inc()
    c.inc(900)
    assert c.exact == 1000
    # 1000 sits within the 16-bit dense head -> the F2P register is exact
    assert c.estimate() == 1000.0
    # same handle back on re-request; duplicate name of a different kind fails
    assert reg.counter("hits") is c
    with pytest.raises(ValueError):
        reg.gauge("hits")


def test_counter_vector_bulk_adds():
    reg = MetricsRegistry("t.vec", register=False)
    v = reg.counter_vector("loads", 8)
    v.add(np.array([0, 3, 3]), np.array([5, 7, 7]))
    assert v.exact.tolist() == [5, 0, 0, 14, 0, 0, 0, 0]
    est = v.estimates()
    assert est.shape == (8,)
    np.testing.assert_allclose(est, v.exact, rtol=0.05)


@pytest.mark.parametrize("n_bits,tol", [(8, 0.35), (16, 0.05)])
def test_histogram_quantiles_vs_exact_oracle(n_bits, tol):
    """Quantiles from F2P-estimated log buckets track np.quantile within
    bucket resolution + counting noise: tight at 16 bits (dense-head exact
    to 4096 per cell), a few 8-bit cells run estimative at this volume."""
    rng = np.random.default_rng(0)
    v = rng.lognormal(3.0, 1.0, 20000)
    reg = MetricsRegistry("t.hist", n_bits=n_bits, register=False)
    h = reg.histogram("lat_ms", 0.1, 1e5, per_decade=16)
    h.observe(v)
    assert h.count == v.size
    assert h.mean == pytest.approx(v.mean(), rel=1e-6)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(v, q))
        assert h.quantile(q) == pytest.approx(exact, rel=tol), f"p{q}"
    # the exact-shadow quantile is bucket-resolution only (no F2P noise)
    assert h.quantile(0.5, exact=True) == pytest.approx(
        float(np.quantile(v, 0.5)), rel=0.16)


def test_histogram_under_overflow_and_scalar_observe():
    reg = MetricsRegistry("t.uo", register=False)
    h = reg.histogram("h", 1.0, 100.0)
    h.observe(0.01)        # underflow
    h.observe(1e6)         # overflow
    h.observe([5.0, 50.0])
    assert h.count == 4
    c = h.counts(exact=True)
    assert c[0] == 1 and c[-1] == 1
    assert h.quantile(0.0) == pytest.approx(1.0)    # clamped to lo
    assert h.quantile(1.0) == pytest.approx(100.0)  # clamped to hi


def test_histogram_device_lazy_sync():
    pytest.importorskip("jax")
    import jax.numpy as jnp

    reg = MetricsRegistry("t.dev", register=False)
    h = reg.histogram("d", 1.0, 1e4)
    vals = np.random.default_rng(1).lognormal(2.0, 1.0, 512)
    h.observe(jnp.asarray(vals[:256]))
    h.observe(jnp.asarray(vals[256:]))
    assert h._dev_pending, "device observes must park, not sync eagerly"
    assert h.count == 512                        # first read drains
    assert not h._dev_pending
    assert h.sum == pytest.approx(vals.astype(np.float32).sum(), rel=1e-4)


def test_registry_reset_and_export_schema():
    reg = MetricsRegistry("t.exp", register=False)
    reg.counter("c").inc(7)
    reg.gauge("g").set(3.5)
    reg.histogram("h", 0.1, 10.0).observe([0.5, 5.0])
    out = reg.export(buckets=True)
    assert out["counters"]["c"] == {"exact": 7, "estimate": 7.0}
    assert out["gauges"]["g"] == 3.5
    hh = out["histograms"]["h"]
    assert hh["count"] == 2 and "p99" in hh and "bucket_counts" in hh
    json.dumps(out)                              # JSON-serializable
    reg.reset()
    out = reg.export()
    assert out["counters"]["c"]["exact"] == 0
    assert out["histograms"]["h"]["count"] == 0
    assert out["gauges"]["g"] == 0.0


def test_process_wide_export_collects_registries():
    reg = MetricsRegistry("t.live")                  # registered
    reg.counter("n").inc(3)
    snap = obs.export()
    assert snap["registries"]["t.live"]["counters"]["n"]["exact"] == 3
    assert snap["trace"] is None                     # tracing disarmed
    del reg


def test_device_backend_advance_matches_exact_in_dense_head():
    pytest.importorskip("jax")
    reg = MetricsRegistry("t.dev_adv", backend="xla", register=False)
    c = reg.counter("n")
    c.inc(3000)                   # inside the 16-bit dense head: exact
    assert c.estimate() == 3000.0 and c.exact == 3000


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering():
    tr = SpanTracer()
    with tr.span("outer", tid=1, req=7):
        with tr.span("inner", tid=1):
            pass
        with tr.span("inner2", tid=1):
            pass
    evs = [e for e in tr.events if e["ph"] == "X"]
    byname = {e["name"]: e for e in evs}
    # children close before the parent -> appended first
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    # containment (what Perfetto nests by): child windows inside the parent
    o, i1, i2 = byname["outer"], byname["inner"], byname["inner2"]
    assert o["ts"] <= i1["ts"] and i1["ts"] + i1["dur"] <= o["ts"] + o["dur"]
    assert i1["ts"] + i1["dur"] <= i2["ts"]          # siblings ordered
    assert o["args"] == {"req": 7}


def test_chrome_trace_schema(tmp_path):
    tr = SpanTracer()
    tr.process_name("engine")
    tr.thread_name(2, "req 1")
    with tr.span("work", tid=2):
        tr.instant("mark", tid=2, uid=1)
    tr.counter("slots", active=3)
    tr.complete("retro", 10.0, 5.0, tid=2)
    p = tmp_path / "t.trace.json"
    tr.write_chrome(str(p))
    doc = json.loads(p.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"M", "X", "i", "C"}
    for e in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] == "C":
            assert all(isinstance(v, float) for v in e["args"].values())
    s = tr.summary()
    assert s["spans"]["work"]["count"] == 1


def test_disabled_path_is_noop():
    assert not obs.enabled() and obs.get() is None
    ctx = obs.span("anything", uid=1)
    assert ctx is obs.span("other")               # the shared null context
    with ctx:
        pass
    obs.instant("x")
    obs.counter_event("c", v=1)
    st = obs.enable(trace=True)
    assert obs.enabled() and obs.get() is st
    with obs.span("real"):
        pass
    assert len(st.tracer) == 1
    obs.disable()
    assert obs.span("again") is ctx


# ---------------------------------------------------------------------------
# compat trackers (the old repro.telemetry API on obs primitives)
# ---------------------------------------------------------------------------
def test_flow_stats_compat():
    fs = FlowStats(["tokens_in", "steps"])
    fs.add("tokens_in", 100)
    fs.add("steps")
    snap = fs.snapshot()
    assert snap["tokens_in"] == pytest.approx(100, rel=0.05)
    assert snap["steps"] == pytest.approx(1)
    from repro.telemetry import FlowStats as Old
    assert Old is FlowStats                      # the shim re-exports


def test_expert_load_tracker_compat():
    t = ExpertLoadTracker(4, n_bits=16)
    t.update(np.array([100, 0, 50, 0]))
    t.update(np.array([100, 0, 0, 0]))
    loads = t.loads()
    assert loads[0] == pytest.approx(200, rel=0.1)
    assert loads[1] == 0
    assert t.imbalance() > 1.0
    # private registries stay out of the process-wide export
    assert not any(k.startswith("telemetry.")
                   for k in obs.export()["registries"])


# ---------------------------------------------------------------------------
# engine integration (serve / fl / sketch)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_setup():
    jax = pytest.importorskip("jax")
    from repro.configs import smoke_config
    from repro.models import init_params

    cfg = smoke_config("llama3_2_3b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _reqs(cfg, n=4, seed=3):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 13))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(4, 9)), arrival=2 * u)
            for u in range(n)]


def test_engine_bitwise_identical_tracing_on_vs_off(serve_setup):
    """The acceptance pin: arming tracing+metrics must not flip one output
    token, and the stats compat view must match the registry export."""
    from repro.serve import BatchedEngine, BatchedServeConfig

    cfg, params = serve_setup
    reqs = _reqs(cfg)
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32), params)
    off = eng.run(reqs)
    stats_off = dict(eng.stats)
    obs.enable(trace=True)
    on = eng.run(reqs)
    tracer = obs.get().tracer
    obs.disable()
    for r in reqs:
        np.testing.assert_array_equal(off[r.uid], on[r.uid])
    # deterministic engine counts identical traced vs untraced
    stats_on = eng.stats
    for k in ("prefills", "rounds", "steps", "emitted_tokens",
              "productive_slot_steps", "slot_occupancy"):
        assert stats_on[k] == stats_off[k], k
    # stats view == registry exact shadows
    snap = eng.metrics.export()
    assert snap["counters"]["prefills"]["exact"] == stats_on["prefills"]
    assert snap["counters"]["emitted_tokens"]["exact"] == \
        stats_on["emitted_tokens"]
    assert snap["histograms"]["ttft_ms"]["count"] == len(reqs)
    assert snap["histograms"]["ttft_ms"]["p50"] > 0
    # the traced run produced per-request rows + engine timeline events
    names = {e["name"] for e in tracer.events}
    assert {"round", "prefill", "admit", "retire", "ttft",
            "decode"} <= names
    uids = {e["args"]["uid"] for e in tracer.events
            if e["ph"] == "X" and e["name"] == "ttft"}
    assert uids == {r.uid for r in reqs}


def test_engine_stats_view_includes_event_keys_lazily(serve_setup):
    """Event keys appear only once nonzero (old `.get(k, 0) + 1` semantics)
    and preemption runs still report exact counts through the view."""
    from repro.serve import BatchedEngine, BatchedServeConfig, Request

    cfg, params = serve_setup
    rng = np.random.default_rng(7)
    reqs = [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 13))
                                        ).astype(np.int32),
                    max_new=16)
            for u in range(5)]
    eng = BatchedEngine(cfg, BatchedServeConfig(slots=2, max_seq=32,
                                                sync_every=4,
                                                preempt_patience=1), params)
    eng.run(reqs)
    st = eng.stats
    assert st["prefills"] == len(reqs)
    assert st.get("preemptions", 0) > 0
    assert st.get("readmits", 0) > 0
    # every request page freed at retirement; paged decode keeps only the
    # engine-lifetime dump page (DESIGN.md §14.2) allocated
    assert st["pool"]["used"] == (1 if eng.paged else 0)
    # tbt histogram saw the multi-token requests
    assert eng.metrics["tbt_ms"].count == len(reqs)
    # queue-wait recorded once per admission
    assert eng.metrics["queue_wait_ms"].count == len(reqs)


def test_fleet_rounds_export_matches_hist():
    from repro.fl import ClientConfig, FleetConfig, run_fleet_rounds, toy_task

    task = toy_task(d_model=16, n_layers=1, vocab=64, seq_len=8, batch=2)
    flcfg = FleetConfig(n_clients=8, sample=6, quorum=2, rounds=2,
                        client=ClientConfig(local_steps=1,
                                            scale_mode="pow2",
                                            error_feedback=False),
                        client_batch=3)
    hist = run_fleet_rounds(flcfg, task)
    snap = obs.export()["registries"]["fl.fleet"]
    assert snap["counters"]["rounds"]["exact"] == 2
    assert snap["counters"]["admitted"]["exact"] == sum(hist["admitted"])
    assert snap["counters"]["wire_bytes"]["exact"] == \
        sum(hist["wire_bytes_per_round"])
    assert snap["gauges"]["wire_bytes_last_round"] == \
        hist["wire_bytes_per_round"][-1]
    assert snap["gauges"]["eval_loss_last"] == hist["eval_loss"][-1]
    # every delivered update logged an arrival lag
    assert snap["histograms"]["arrival_lag_s"]["count"] >= sum(hist["admitted"])


def test_fed_avg_export():
    from repro.fl import ClientConfig, FedAvgConfig, run_fed_avg, toy_task

    task = toy_task(d_model=16, n_layers=1, vocab=64, seq_len=8, batch=2)
    fcfg = FedAvgConfig(n_clients=2, rounds=2,
                        client=ClientConfig(local_steps=1))
    hist = run_fed_avg(fcfg, task)
    snap = obs.export()["registries"]["fl.fedavg"]
    assert snap["counters"]["rounds"]["exact"] == 2
    assert snap["counters"]["wire_bytes"]["exact"] == \
        sum(hist["wire_bytes_per_round"])
    assert snap["gauges"]["eval_loss_last"] == hist["eval_loss"][-1]


def test_sketch_ingest_instrumentation():
    pytest.importorskip("jax")
    from repro.serve import SketchIngestEngine
    from repro.sketch import F2PSketch, SketchConfig

    sk = F2PSketch(SketchConfig(depth=2, width=256, n_bits=8))
    eng = SketchIngestEngine(sk, batch=128)
    rng = np.random.default_rng(0)
    eng.ingest(rng.integers(0, 1000, 300))
    eng.flush()
    assert eng.packets == 300                     # exact int (test contract)
    assert eng.batches == 3                       # 2 full + 1 padded tail
    snap = eng.metrics.export()
    assert snap["counters"]["packets"]["exact"] == 300
    assert snap["gauges"]["arrivals_per_s"] > 0
    # the partial tail (300 - 256 = 44) hit the flush-depth histogram
    assert snap["histograms"]["flush_depth"]["count"] == 1
    assert eng.stats()["packets"] == 300
