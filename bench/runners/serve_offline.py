"""Offline batch jobs through ``BatchedEngine.run``.

A job is one ``run(requests)`` call over the cell's ``job_requests``
requests, all there at once. Jobs start back to back until ``seconds`` have
passed, and the job in flight always finishes: the window runs from the
first job's start to the last job's end, drain tails included.

End-to-end metrics: ``out_tok_s``, the output tokens of every request in
the window over its seconds; ``tpot_ms``, the window's milliseconds over
the decode steps the engine counted in it (``stats["steps"]``); and
``setup_s``, process start until the window opens.

``correct``: every request returned ``max_new`` tokens inside the
vocabulary, and on a sample of the window's requests drawn from the seed
(the longest among them), where each served token's float32 reference
logit lies some gap below the reference's best logit at its position, the
mean gap is at most the cell's ``mean_gap``. (The widest gap swings from
seed to seed by more than the control departs from the program, so it is
logged, not compared.) :func:`control` puts the lower-precision control's
tokens in the program's place and judges them by the same checks.

With ``trace`` the first job runs under ``jax.profiler`` with the engine's
``obs`` spans annotated into the trace; the per-layer readers get a
:class:`ServeTrace` of it.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import time

import numpy as np

from bench import harness, trace_reduce, weights, work
from bench.reference import dense

ENGINE_SPANS = ("round", "prefill", "prefill_group")
JOB_SPAN = "bench_job"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class ServeTrace:
    """What the per-layer readers see of the traced job."""
    device: trace_reduce.DeviceTrace
    host_window_s: float
    spans: list[tuple[str, float, float]]   # engine spans, s from job start
    rounds: list[dict]                       # "slots" counter per round
    prompt_lens: list[int]
    output_lens: list[int]
    model: dict
    sync_every: int
    page_tokens: int
    kv_bits: int
    peaks: dict


@dataclasses.dataclass
class RunResult:
    e2e: dict
    attempted: int
    failed: int
    checks: dict
    correct: bool
    memory_peak_bytes: int
    done: list = dataclasses.field(default_factory=list)
    sample: list = dataclasses.field(default_factory=list)
    gaps: np.ndarray | None = None
    trace_ctx: ServeTrace | None = None
    breakdown: dict | None = None


def program_config(name: str, m: dict):
    from repro.models.config import ModelConfig, dense_pattern

    return ModelConfig(
        name=name, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        pattern=dense_pattern(), rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=m["torch_dtype"], fused_attention=True)


def check_layout(cfg, params) -> None:
    """The benchmark's weights must have the program's parameter layout."""
    import jax

    from repro.models import init_params

    want = jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")


# -- warm-up ---------------------------------------------------------------
def _powers_to(n: int) -> list[int]:
    out, g = [], 1
    while g < n:
        out.append(g)
        g *= 2
    return out + [n]


def warm_plan(eng, bscfg, prompt_lens, output_lens) -> list[list[tuple]]:
    """Batches of (prompt length, max_new) that make the engine compile
    every shape the cell's traffic can reach: each prompt length in a
    prefill group of every size the engine forms, and one decode round at
    every page span the live lengths can need. Each batch is admitted on
    its own (arrivals far apart)."""
    T, S, sync = eng.page_tokens, bscfg.max_seq, bscfg.sync_every
    maxp = S // T
    lens = sorted({int(x) for x in prompt_lens})

    def bucket(L):
        return next((b for b in eng.buckets if L <= b), -(-L // T) * T)

    batches: list[list[tuple]] = []
    for g in _powers_to(max(1, bscfg.prefill_group)):
        if g > bscfg.slots:
            break
        by_b: dict[int, list[int]] = {}
        for L in lens:
            by_b.setdefault(bucket(L), []).append(L)
        packed: list[dict[int, list[int]]] = []
        for b, ls in by_b.items():
            for i in range(0, len(ls), g):
                grp = (ls[i:i + g] * g)[:g]    # a group of exactly g
                dst = next((p for p in packed if b not in p and
                            sum(map(len, p.values())) + g <= bscfg.slots),
                           None)
                if dst is None:
                    dst = {}
                    packed.append(dst)
                dst[b] = grp
        batches += [[(L, 1) for grp in p.values() for L in grp]
                    for p in packed]

    def need(L, r):
        end = min(L + sync * r + sync - 1, S - 1)
        return min(end // T + 1, maxp)

    spans = _powers_to(maxp)[1:]        # the engine's buckets: 2, 4, ..., maxp

    def span(n):
        return next(b for b in spans if b >= n)

    rounds = lambda M: max(1, math.ceil((M - 1) / sync))
    lo = min(need(L, 0) for L in lens)
    hi = need(lens[-1], rounds(int(max(output_lens))) - 1)
    for s in sorted({span(n) for n in range(lo, hi + 1)}):
        L = next((L for L in lens if span(need(L, 0)) == s), None)
        if L is not None:
            batches.append([(L, 2)])
            continue
        L = lens[-1]
        r = next(r for r in range(maxp) if span(need(L, r)) >= s)
        batches.append([(L, min(sync * r + 2, S - L))])
    return batches


def warm_up(eng, bscfg, traffic, vocab: int, log) -> None:
    from repro.serve import Request

    rng = np.random.default_rng(0)
    reqs, uid = [], 0
    plan = warm_plan(eng, bscfg, traffic.prompt_lens, traffic.output_lens)
    for k, batch in enumerate(plan):
        for L, M in batch:
            uid += 1
            reqs.append(Request(uid=uid, max_new=M, arrival=k * 10 ** 6,
                                tokens=rng.integers(0, vocab, L,
                                                    dtype=np.int32)))
    t0 = time.perf_counter()
    eng.run(reqs)
    log(f"warm-up: {len(reqs)} requests in {len(plan)} batches, "
        f"{time.perf_counter() - t0:.3f} s")


# -- the comparison --------------------------------------------------------
def sample(done: list, n: int, seed: int) -> list:
    """The longest request and n - 1 others drawn from the seed."""
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i][0].prompt) + done[i][0].max_new))
    rest = order[1:]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[order[0]]] + [done[rest[i]] for i in sorted(pick)]


def reference_shape(mix: dict) -> tuple[int, int]:
    """(sequence length, served positions) of the reference's inputs: the
    longest prompt with the longest output, so one program serves every
    sample."""
    return (int(mix["prompt"]["max"] + mix["output"]["max"]),
            int(mix["output"]["max"]))


def reference_gaps(seed: int, m: dict, picked: list, seq: int, width: int,
                   precision: str = "f32"):
    """Per served token: how far the reference's logit of that token lies
    below the reference's best at its position ([n, width], NaN where no
    token), and the tokens judged ([n, width]). With the control's
    precision (``dense.CONTROL``) those are the tokens the control puts
    first at the same positions of the same prompts and served tokens."""
    import jax.numpy as jnp

    n = len(picked)
    toks = np.zeros((n, seq), np.int32)
    read = np.zeros((n, width), np.int32)
    served = np.zeros((n, width), np.int32)
    valid = np.zeros((n, width), bool)
    for i, (r, out) in enumerate(picked):
        L, M = len(r.prompt), len(out)
        toks[i, :L] = r.prompt
        toks[i, L:L + M - 1] = out[:-1]
        read[i, :M] = np.arange(L - 1, L + M - 1)
        served[i, :M] = out
        valid[i, :M] = True
    ref = dense.logits_at(seed, m, toks, read)
    if precision != "f32":
        low = dense.logits_at(seed, m, toks, read, precision=precision)
        served = np.asarray(jnp.argmax(low, -1))
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
    gaps = np.asarray(best - got, np.float64)
    return np.where(valid, gaps, np.nan), np.asarray(served)


def judge(done: list, gaps, chk: dict, vocab: int) -> tuple[dict, bool]:
    """The checks that decide ``correct``, each beside its limit."""
    checks = {"failed": {"value": count_failed(done, vocab), "limit": 0},
              "mean_gap": {"value": float(np.nanmean(gaps)),
                           "limit": chk["mean_gap"]}}
    return checks, len(done) > 0 and all(c["value"] <= c["limit"]
                                         for c in checks.values())


def control(cell, res: RunResult, seed: int) -> tuple[dict, bool]:
    """``correct`` with the control in the program's place: each sampled
    request answers with the tokens the control puts first (at the same
    positions of the same prompts and served tokens), judged by the same
    checks as a run. The benchmark's own runs never call this."""
    m = harness.model_dims(cell.config)
    gaps, toks = reference_gaps(seed, m, res.sample,
                                *reference_shape(cell.traffic),
                                precision=dense.CONTROL)
    by_uid = {r.uid: toks[i, :len(out)]
              for i, (r, out) in enumerate(res.sample)}
    done = [(r, by_uid.get(r.uid, out)) for r, out in res.done]
    return judge(done, gaps, cell.spec["check"], m["vocab_size"])


def count_failed(done: list, vocab: int) -> int:
    bad = 0
    for r, out in done:
        out = np.asarray(out) if out is not None else np.zeros(0)
        if (out.shape != (r.max_new,) or out.min() < 0
                or out.max() >= vocab):
            bad += 1
    return bad


# -- tracing ---------------------------------------------------------------
def _trace_dir() -> str:
    return os.path.join(harness.ROOT, ".bench_out", "trace")


def traced_job(eng, reqs):
    """Run one job under the profiler with the engine's spans armed.
    Returns (outputs, host seconds, engine spans, round counters)."""
    import jax

    from repro import obs

    d = _trace_dir()
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    state = obs.enable(annotate=True)
    tr = state.tracer
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        t0 = tr.now_us()
        with jax.profiler.TraceAnnotation(JOB_SPAN):
            out = eng.run(reqs)
        t1 = tr.now_us()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    spans = [(e["name"], (e["ts"] - t0) / 1e6,
              (e["ts"] + e["dur"] - t0) / 1e6)
             for e in tr.events if e["ph"] == "X" and e.get("tid") == 0
             and e["name"] in ENGINE_SPANS]
    rounds = [e["args"] for e in tr.events
              if e["ph"] == "C" and e["name"] == "slots"]
    return out, (t1 - t0) / 1e6, spans, rounds


# -- the run ---------------------------------------------------------------
def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        log) -> RunResult:
    import jax

    from repro.serve import BatchedEngine, BatchedServeConfig, Request

    m = harness.model_dims(cell.config)
    cfg = program_config(cell.spec["config"], m)
    settings = {k: tuple(v) if isinstance(v, list) else v
                for k, v in cell.spec["serve"].items()}
    bscfg = BatchedServeConfig(**settings)
    traffic = cell.generator.make(cell.traffic,
                                  job_requests=cell.spec["job_requests"],
                                  vocab=m["vocab_size"], seed=seed)
    t0 = time.perf_counter()
    params = jax.block_until_ready(weights.program_params(seed, m))
    check_layout(cfg, params)
    log(f"weights: {time.perf_counter() - t0:.3f} s")
    eng = BatchedEngine(cfg, bscfg, params)
    warm_up(eng, bscfg, traffic, m["vocab_size"], log)
    pool = eng.pool.stats()
    log(f"pool: {pool['n_pages']} pages of {pool['page_tokens']} tokens, "
        f"{pool['pool_bytes_packed']} B")

    compiles = []

    def listen(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    done, steps, ctx_parts = [], 0, None
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t_open = time.perf_counter()
        j = 0
        while True:
            job = traffic.job(j)
            reqs = [Request(uid=r.uid, tokens=r.prompt, max_new=r.max_new)
                    for r in job]
            if trace and j == 0:
                out, job_s, spans, rounds = traced_job(eng, reqs)
                ctx_parts = (job_s, spans, rounds, job)
            else:
                out = eng.run(reqs)
            steps += eng.stats["steps"]
            done += [(r, out.get(r.uid)) for r in job]
            j += 1
            if time.perf_counter() - t_open >= seconds:
                break
        t_close = time.perf_counter()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    window = t_close - t_open
    out_tokens = sum(len(o) for _, o in done if o is not None)
    log(f"window: {window:.3f} s, {j} jobs, {len(done)} requests, "
        f"{out_tokens} output tokens, {steps} decode steps, "
        f"{len(compiles)} compile events inside")
    e2e = {"out_tok_s": out_tokens / window,
           "tpot_ms": window * 1e3 / max(steps, 1),
           "setup_s": t_open - t_start}
    peak = harness.peak_memory(1)
    kv_bits = eng.pool.slabs[eng.pool.attn_keys[0]]["k"].fmt.n_bits
    del eng, params
    gc.collect()

    chk = cell.spec["check"]
    t0 = time.perf_counter()
    good = [d for d in done if d[1] is not None]
    picked = sample(good, chk["sequences"], seed)
    gaps, _ = reference_gaps(seed, m, picked, *reference_shape(cell.traffic))
    log(f"reference: {np.isfinite(gaps).sum()} served tokens compared in "
        f"{time.perf_counter() - t0:.3f} s; widest gap {np.nanmax(gaps)!r} "
        f"(information)")
    checks, correct = judge(done, gaps, chk, m["vocab_size"])
    res = RunResult(e2e=e2e, attempted=len(done),
                    failed=checks["failed"]["value"], checks=checks,
                    correct=correct, memory_peak_bytes=peak, done=done,
                    sample=picked, gaps=gaps)
    if trace:
        job_s, spans, rounds, job_reqs = ctx_parts
        dev = trace_reduce.reduce(trace_reduce.find_xplane(_trace_dir()),
                                  window=JOB_SPAN, spans=ENGINE_SPANS)
        res.trace_ctx = ServeTrace(
            device=dev, host_window_s=job_s, spans=spans, rounds=rounds,
            prompt_lens=[len(r.prompt) for r in job_reqs],
            output_lens=[r.max_new for r in job_reqs], model=m,
            sync_every=bscfg.sync_every, page_tokens=pool["page_tokens"],
            kv_bits=kv_bits,
            peaks=work.peaks(jax.devices()[0].device_kind))
        res.breakdown = {"device_ops": dev.top_ops(10),
                         "idle_gaps": dev.idle_gaps(10)}
    return res
