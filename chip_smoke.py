"""Bring-up check of the main paths on a TPU, one process.

    python chip_smoke.py            # one chip: serve llama-3.2-3B
    python chip_smoke.py --chips 4  # four chips: data-parallel training

One chip: ``llama3_2_3b.full()`` (28 layers, d=3072, GQA 24/8, vocab
128256, bf16, random weights from seed 0) serves a handful of mixed-length
requests through ``BatchedEngine`` over the paged, packed 8-bit F2P KV pool,
with ``attention_paged`` and the KV-write quantize as compiled Pallas
kernels. The same requests then run with ``F2P_BACKEND=xla`` (the kernels'
XLA twins), and one paged decode step is compared at the logits.

Four chips: ``xlstm_125m.full()`` trains a few steps with F2P gradient
compression on a ("data", "model") = (4, 1) mesh, and the per-step losses
are compared with the same steps on one chip and the same global batch.

Diagnostics go to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or outside a checkout of the repository, it exits non-zero
and prints no result. Compile and run seconds are printed as information,
not as metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# serving phase: full slot count and cache length; prompts all fall in the
# 128-token prefill bucket and every request ends within 16 pages, so the
# run compiles one prefill shape and one decode-round shape per backend
SLOTS, MAX_SEQ, PAGE_SPAN = 32, 2048, 16
PROMPT_LENS = (66, 71, 77, 83, 88, 93, 97, 100)
MAX_NEW = (8, 20, 12, 16, 20, 8, 16, 12)
SERVE_OPS = ("attention_paged", "quantize_packed")
KERNEL_OPS = ("attention_paged", "attention_packed", "quantize",
              "dequantize", "quantize_packed", "dequantize_packed",
              "dequant_matmul", "dequant_matmul_packed", "counter_advance",
              "counter_estimate")

# training phase: global batch divides the data axis; the loss tolerance
# is bf16 activations reduced in a different order on 4 shards vs 1
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ, LOSS_RTOL = 4, 16, 512, 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(vocab: int, seed: int = 0):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i + 1,
                    tokens=rng.integers(0, vocab, n).astype(np.int32),
                    max_new=m)
            for i, (n, m) in enumerate(zip(PROMPT_LENS, MAX_NEW))]


def check_outputs(reqs, out: dict, vocab: int) -> None:
    """Every request returns exactly its max_new tokens, all in range."""
    import numpy as np

    for r in reqs:
        toks = np.asarray(out[r.uid])
        if toks.shape != (r.max_new,):
            raise AssertionError(f"request {r.uid}: {toks.shape[0]} tokens, "
                                 f"want {r.max_new}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {r.uid}: token out of range")


def resolved_backends() -> dict:
    from repro.kernels import (dispatch, f2p_attention, f2p_counter,  # noqa
                               f2p_matmul, f2p_quant)

    return {op: dispatch.resolve_backend(op=op) for op in KERNEL_OPS}


def decode_round_hlo(eng):
    """Compile the engine's decode round at the span this run used: HLO
    text and memory analysis of the program the engine runs."""
    pages = eng.pages[:, :PAGE_SPAN]
    compiled = eng._round.lower(eng.params, eng.caches, eng.tok, eng.pos,
                                eng.req, pages).compile()
    return compiled.as_text(), compiled.memory_analysis()


def serve_once(cfg, params, reqs, label: str):
    from repro.serve import BatchedEngine, BatchedServeConfig

    eng = BatchedEngine(cfg, BatchedServeConfig(slots=SLOTS, max_seq=MAX_SEQ),
                        params)
    t0 = time.perf_counter()
    out = eng.run(reqs)
    log(f"[{label}] first run incl. compile: "
        f"{time.perf_counter() - t0:.3f} s (information only)")
    return eng, out


def decode_logits(cfg, params, reqs, backend: str):
    """Prefill every prompt into a small pool (default backend), then one
    paged decode step under ``backend``: [len(reqs), vocab] logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import decode_step, init_caches, prefill
    from repro.serve.paging import PagedKVPool

    T, bucket = 8, 128
    pool = PagedKVPool(cfg, T, len(reqs) * PAGE_SPAN + 1)
    pf = jax.jit(lambda p, t, c, i: prefill(p, {"tokens": t}, cfg, c,
                                             last_index=i))
    pages = np.zeros((len(reqs), PAGE_SPAN), np.int32)
    first = []
    for b, r in enumerate(reqs):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(r.tokens)] = r.tokens
        caches = init_caches(cfg, 1, bucket, quantized_kv=True,
                             packed_kv=True)
        lg, caches = pf(params, jnp.asarray(toks), caches,
                        jnp.asarray([len(r.tokens) - 1], jnp.int32))
        table = pool.store_prefill(caches, len(r.tokens))
        pages[b, :len(table.pages)] = table.pages
        first.append(int(jnp.argmax(lg[0])))
    slabs = {k: dict(pool.slabs[k]) for k in pool.attn_keys}
    pos = jnp.asarray([len(r.tokens) for r in reqs], jnp.int32)
    old = os.environ.get("F2P_BACKEND")
    os.environ["F2P_BACKEND"] = backend
    try:
        step = jax.jit(lambda p, t, q, c, g: decode_step(p, t, q, c, cfg,
                                                         pages=g))
        logits, _ = step(params, jnp.asarray(first, jnp.int32)[:, None], pos,
                         slabs, jnp.asarray(pages))
        return np.asarray(logits, np.float32)
    finally:
        if old is None:
            del os.environ["F2P_BACKEND"]
        else:
            os.environ["F2P_BACKEND"] = old


def serve_phase(dev) -> None:
    import jax
    import numpy as np

    from repro.configs import full_config
    from repro.models import init_params

    backends = resolved_backends()
    for op, b in backends.items():
        log(f"backend {op} -> {b}")
    bad = {op: backends[op] for op in SERVE_OPS if backends[op] != "pallas"}
    if bad:
        raise AssertionError(f"serving ops not on compiled Pallas: {bad}")

    cfg = dataclasses.replace(full_config("llama3_2_3b"),
                              fused_attention=True)
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=0)(cfg,
                                                    jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    log(f"params {cfg.param_count() / 1e9:.3f}B initialized in "
        f"{time.perf_counter() - t0:.3f} s (information only)")
    reqs = make_requests(cfg.vocab_size)

    eng, out = serve_once(cfg, params, reqs, "pallas")
    check_outputs(reqs, out, cfg.vocab_size)
    t0 = time.perf_counter()
    again = eng.run(reqs)
    log(f"[pallas] warm run: {time.perf_counter() - t0:.3f} s, "
        f"{sum(len(v) for v in again.values())} tokens (information only)")
    if any(not np.array_equal(out[r.uid], again[r.uid]) for r in reqs):
        raise AssertionError("warm run changed greedy tokens")
    hlo, mem = decode_round_hlo(eng)
    n_kernels = hlo.count("tpu_custom_call")
    log(f"decode round HLO: {n_kernels} tpu_custom_call sites")
    if not n_kernels:
        raise AssertionError("no Pallas kernel in the compiled decode round")
    log(f"decode round memory: args {mem.argument_size_in_bytes} B, "
        f"temp {mem.temp_size_in_bytes} B, out {mem.output_size_in_bytes} "
        f"B, aliased {mem.alias_size_in_bytes} B")
    log(f"pool {eng.pool.stats()['pool_bytes_packed']} B packed, "
        f"{eng.pool.n_pages} pages of {eng.page_tokens} tokens")
    for r in reqs:
        log(f"request {r.uid}: prompt {len(r.tokens)} -> "
            f"{out[r.uid].tolist()}")
    del eng
    gc.collect()

    os.environ["F2P_BACKEND"] = "xla"
    try:
        eng, out_x = serve_once(cfg, params, reqs, "xla")
    finally:
        del os.environ["F2P_BACKEND"]
    check_outputs(reqs, out_x, cfg.vocab_size)
    same = [r.uid for r in reqs if np.array_equal(out[r.uid], out_x[r.uid])]
    log(f"pallas vs xla greedy tokens: {len(same)}/{len(reqs)} requests "
        f"identical")
    del eng
    gc.collect()

    lp = decode_logits(cfg, params, reqs, "pallas")
    lx = decode_logits(cfg, params, reqs, "xla")
    log(f"paged decode step logits, pallas vs xla: max |diff| "
        f"{float(np.abs(lp - lx).max())}, max |logit| "
        f"{float(np.abs(lx).max())}, argmax agree "
        f"{int((lp.argmax(-1) == lx.argmax(-1)).sum())}/{len(reqs)}")
    stats = dev.memory_stats() or {}
    log(f"device peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def train_losses(cfg, devices, shape):
    """Per-step losses of TRAIN_STEPS steps on a ("data", "model") mesh of
    ``shape`` over ``devices`` (seed 0 state, the same global batches)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import default_policy
    from repro.data import DataConfig, host_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import rules_for, train_state_sds
    from repro.models.sharding import logical_rules
    from repro.optim import AdamWConfig, CompressionConfig
    from repro.train import init_train_state, make_train_step

    ocfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=TRAIN_STEPS)
    gfmt, gblock = default_policy("xlstm_125m").f2p_for(
        "grad", (CompressionConfig.fmt, 128))
    ccfg = CompressionConfig(enabled=True, min_size=512, fmt=gfmt,
                             block=gblock)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                      global_batch=TRAIN_BATCH)
    mesh = make_mesh(shape, ("data", "model"), devices=devices)
    rules = rules_for(cfg, mesh, "train_4k")
    losses = []
    with logical_rules(rules, mesh):
        state = init_train_state(cfg, ocfg, ccfg, jax.random.PRNGKey(0))
        sds, _ = train_state_sds(cfg, ocfg, ccfg, mesh, rules)
        state = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                             state, sds)
        step = jax.jit(make_train_step(cfg, ocfg, ccfg), donate_argnums=0)
        rows = NamedSharding(mesh, P("data"))
        t0 = time.perf_counter()
        for i in range(TRAIN_STEPS):
            batch = {k: jax.device_put(jnp.asarray(v), rows)
                     for k, v in host_batch(dcfg, i).items()}
            if batch["tokens"].sharding.device_set != set(devices):
                raise AssertionError("batch not spread over the mesh")
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        log(f"[{shape}] {TRAIN_STEPS} steps incl. compile: "
            f"{time.perf_counter() - t0:.3f} s (information only)")
        leaf = jax.tree.leaves(state["params"])[0]
        if leaf.sharding.device_set != set(devices):
            raise AssertionError("train state not on every mesh device")
    return losses


def train_phase(devices) -> None:
    import math

    from repro.configs import full_config
    from repro.launch.mesh import make_host_mesh

    host = make_host_mesh(len(devices))
    if set(host.devices.flat) != set(devices):
        raise AssertionError("make_host_mesh does not span the devices")
    cfg = full_config("xlstm_125m")
    log(f"xlstm-125m: {cfg.param_count() / 1e6:.1f}M params, global batch "
        f"{TRAIN_BATCH} x {TRAIN_SEQ}")
    many = train_losses(cfg, devices, (len(devices), 1))
    one = train_losses(cfg, devices[:1], (1, 1))
    for i, (a, b) in enumerate(zip(many, one)):
        log(f"step {i}: loss {len(devices)} chips {a!r}, 1 chip {b!r}")
        if not (math.isfinite(a) and abs(a - b) <= LOSS_RTOL * abs(b)):
            raise AssertionError(f"step {i}: losses disagree beyond "
                                 f"rtol {LOSS_RTOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke.py: run it from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.chips == 4:
        train_phase(devices[:4])
    else:
        serve_phase(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
