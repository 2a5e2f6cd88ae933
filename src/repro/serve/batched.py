"""Continuous-batching serve engine over the block-paged packed-F2P KV pool
(DESIGN.md §12, §14; ROADMAP item 1).

The sequential :class:`repro.serve.engine.Engine` runs one fixed-shape
request batch start-to-finish; this engine admits a *dynamic* set of
requests into a fixed number of decode **slots** so the jitted decode step
compiles exactly once and every step serves every live request at its own
sequence position (per-slot ``pos``/``kv_len`` threading through
``decode_step`` into the fused attention kernels).

Shape discipline (everything the device sees is fixed-shape):

* decode: one jitted step over ``[slots]`` — per-slot token, position and
  request id vectors; retired slots keep stepping into a clamped dead
  position until a new request joins (their output is discarded host-side).
* prefill: prompts padded to a shape **bucket**, and compatible queued
  prompts grouped into ONE jitted ``[N, bucket]`` call (N rounded to a
  power-of-two group size, dummy rows ignored) — jit specializes per
  (N, bucket), so ragged traffic costs a handful of compiles. Families with
  recurrent state (mamba/xLSTM) scan every input token, so padding would
  pollute the state — their registry entry sets exact-length batch-1
  prefill instead.
* admission (**paged decode**, the default for families with attention KV):
  prefill KV lands in :class:`~repro.serve.paging.PagedKVPool` pages and the
  slot simply ADOPTS the page table — the decode step attends the pool slabs
  in place through a per-slot ``[slots, max_pages]`` page-id table
  (``kernels.f2p_attention.attention_paged``), so no dense
  ``[slots, max_seq]`` KV row exists anywhere and slot KV memory is
  page-granular in the live length. Pages are allocated lazily just ahead of
  the write position each round and trimmed back on preemption.
  ``paged_decode=False`` keeps the PR-8 copy-in engine (pages word-copied
  into a dense slot row and freed) as the bitwise comparator.

Every host<->device sync is batched: the engine runs ``sync_every`` decode
steps back-to-back, then syncs ONE ``[slots, sync_every]`` token chunk and
does all bookkeeping (retirement, admission, preemption) at that boundary.
Host-mirror uploads at the boundary are delta-masked: only slots whose
bookkeeping actually changed overwrite the device vectors (one fused jitted
where per boundary), which is bitwise-invisible vs the full re-upload
(asserted in-bench).

Admission is latency-aware: ready requests are scored by queue-wait age
normalized against the SLO/observed queue-wait histogram (the PR-9 ``obs``
plane feeds the normalizer) minus a projected-decode-tail penalty, so
short-tail requests can jump ahead under light load while aging requests
dominate under pressure. The FIFO starvation bound is preserved as a hard
floor: a request passed over ``preempt_patience`` times scores +inf and must
be admitted next.

Tracing (``obs.enable()``; every site is one ``is None`` probe when off):
the engine row (tid 0) carries phase spans that tile ``run()``:
``schedule`` (visibility stamps, readmission, admission; ``prefill`` /
``prefill_group`` nest in it, each split into ``prefill.launch``,
``prefill.store`` and ``prefill.wait``), ``round`` (``round.prep``: page
growth, slab binding, mirror uploads, span slice; ``round.launch``;
``round.wait``: the host blocked on the token chunk) and ``harvest``
(harvest, retirement, defrag, starvation check, preemption). Counter
events: ``slots`` per round (``active``, ``pool_used``, and the kept
tokens' ``kv_live`` / ``kv_written`` / ``steps_kept``, and ``kv_attended``,
the KV positions the attention covered for them). :data:`PROGRAMS`
names the engine's XLA programs.

Bitwise contract (families with ``exact_cobatch``): per-request greedy
outputs are identical to the sequential engine's — and paged decode is
bitwise-identical to the copy-in engine — pinned by
tests/test_serve_batched.py and examples/serve_continuous.py.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.f2p_attention import decode_order
from repro.models import init_caches
from repro.models.config import ModelConfig
from repro.serve.arch import SupportedArchitecture, arch_for
from repro.serve.paging import HostKV, PagedKVPool, PageTable

__all__ = ["BatchedServeConfig", "BatchedEngine", "Request", "PROGRAMS"]

# The XLA module name of every program the engine (and its page pool)
# dispatches -> the layer a profile's device time is put down to. A device
# op of any other module (eager argmax, converts, slices) reads as "other".
# kv_store pages a prefill's KV into the pool; kv_move copies pages that
# already hold KV (defrag, park/readmit, copy-in). Renaming a jitted
# function renames its module: tests pin this table.
PROGRAMS = {
    "jit_round_fn": "round",
    "jit_prefill_step": "prefill",
    "jit__store_row_all": "kv_store",
    "jit__load_row_all": "kv_move",
    "jit__leaf_set_slot": "kv_move",
    "jit__scatter_pages": "kv_move",
    "jit__gather_pages": "kv_move",
    "jit__move_pages_all": "kv_move",
    "jit__io_delta": "upload",
    "jit__pages_delta": "upload",
}


@dataclasses.dataclass(frozen=True)
class BatchedServeConfig:
    slots: int                    # decode lanes (the fixed device batch)
    max_seq: int                  # per-slot cache length (multiple of page)
    eos: int = -1                 # per-request EOS (device chunk-synced)
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0                 # sampling stream root (folded per request)
    kv_policy: Any = None         # per-layer KV formats (FormatPolicy|None)
    page_tokens: int | None = None     # None = family default
    n_pages: int | None = None         # None = mode-dependent default
    prefill_buckets: tuple[int, ...] | None = None  # None = family default
    sync_every: int = 8           # decode steps per host sync
    preempt_patience: int = 2     # sync rounds a ready request starves
                                  # before the longest-tail slot is preempted
                                  # (also the scheduler's pass-over bound)
    evict_parked_to_host: bool = True  # parked KV goes to host numpy
                                       # (pages reclaimed immediately)
    paged_decode: bool | None = None   # attend page tables in place; None =
                                       # on for families with attention KV
    io_upload: str = "delta"      # "delta" | "full" boundary mirror upload
    scheduler: str = "slo"        # "slo" | "fifo" admission ordering
    slo_ttft_ms: float = 1000.0   # admission score: target queue-wait norm
    sched_tail_weight: float = 0.25    # projected-tail penalty weight
    prefill_group: int = 4        # max prompts fused per prefill call
    defrag_every: int = 0         # compact the pool every N rounds (0=never)


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray            # prompt [L]
    max_new: int
    arrival: int = 0              # global decode-step index of visibility


@dataclasses.dataclass
class _Slot:
    uid: int
    prompt_len: int
    max_new: int
    tokens: list[int]


@dataclasses.dataclass
class _Parked:
    uid: int
    prompt_len: int
    max_new: int
    tokens: list[int]
    pos: int                      # next decode write position
    last_tok: int
    table: PageTable | None = None
    host: HostKV | None = None
    state: Any = None             # recurrent per-slot leaves (host numpy)


@functools.partial(jax.jit, donate_argnums=(0,))
def _leaf_set_slot(full, one, slot):
    """Recurrent cache leaf [G, B, ...] row <- one [G, 1, ...]."""
    start = (jnp.int32(0), slot) + (jnp.int32(0),) * (full.ndim - 2)
    return jax.lax.dynamic_update_slice(full, one.astype(full.dtype), start)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _io_delta(tok, pos, req, mask, tok_n, pos_n, req_n):
    """Delta-masked mirror upload: only dirty slots overwrite the device
    vectors (ONE fused dispatch). Bitwise-invisible vs a full re-upload
    because the host mirrors are kept in lockstep with the device clamp."""
    return (jnp.where(mask[:, None], tok_n[:, None], tok),
            jnp.where(mask, pos_n, pos),
            jnp.where(mask, req_n, req))


@functools.partial(jax.jit, donate_argnums=(0,))
def _pages_delta(pages, mask, pages_n):
    return jnp.where(mask[:, None], pages_n, pages)


def _upload(mirror: np.ndarray):
    """Device copy of a host mirror. The mirrors and dirty masks are mutated
    in place right after the upload is dispatched, and the host-to-device
    copy of a numpy array may still be in flight then: on the CPU backend a
    cleared mask leaked into in-flight uploads (slots decoding a stale token
    under load). Uploading a private copy removes the race."""
    return jnp.asarray(mirror.copy())


class BatchedEngine:
    """Continuous-batching engine; see module docstring. ``run(requests)``
    returns {uid: np.int32 tokens} plus fills ``self.stats``."""

    def __init__(self, cfg: ModelConfig, bscfg: BatchedServeConfig, params):
        self.arch: SupportedArchitecture = arch_for(cfg)
        if self.arch.paged_kv and not cfg.fused_attention:
            cfg = dataclasses.replace(cfg, fused_attention=True)
        self.cfg, self.bscfg, self.params = cfg, bscfg, params
        B, S = bscfg.slots, bscfg.max_seq
        T = bscfg.page_tokens or self.arch.page_tokens
        if S % T:
            raise ValueError(f"max_seq {S} not a multiple of page_tokens {T}")
        self.page_tokens = T
        self.paged = (self.arch.paged_kv if bscfg.paged_decode is None
                      else bool(bscfg.paged_decode) and self.arch.paged_kv)
        self.pool = None
        self._dump = 0                      # reserved garbage page (paged)
        self._tables: list[PageTable | None] = [None] * B
        maxp = S // T
        if self.arch.paged_kv:
            n_pages = bscfg.n_pages
            if n_pages is None:
                if self.paged:
                    # the pool IS the only KV home: size it to the same
                    # worst-case capacity the copy-in engine's dense caches
                    # hold (B slots x maxp pages), +maxp so one admission
                    # can stage while every slot is full-length, +1 for the
                    # reserved dump page. Parked slots either trim to their
                    # live prefix or evict to host, so this bound holds
                    # under preemption churn too; callers oversubscribing
                    # with evict_parked_to_host=False should pass n_pages.
                    n_pages = (B + 1) * maxp + 1
                else:
                    n_pages = B * maxp + maxp   # all slots + one transit
            self.pool = PagedKVPool(cfg, T, n_pages,
                                    kv_policy=bscfg.kv_policy)
            # how the attention kernels decode this pool's formats
            # (storage-order planes or the per-head unpack), for stats
            orders = {decode_order(self.pool.slabs[key][kv].fmt)
                      for key in self.pool.attn_keys for kv in ("k", "v")}
            self.attn_decode_order = "/".join(sorted(orders)) or None
            if self.paged:
                # page 0, allocated for the engine's lifetime: retired slot
                # rows point here and their clamped dead-position writes land
                # here; its contents are never read (masked or discarded)
                (self._dump,) = self.pool.alloc(1)
        self.caches = init_caches(cfg, B, S,
                                  quantized_kv=self.arch.paged_kv,
                                  kv_policy=bscfg.kv_policy,
                                  packed_kv=True if self.arch.paged_kv
                                  else None,
                                  attn_kv=not self.paged)
        self.tok = jnp.zeros((B, 1), jnp.int32)
        self.pos = jnp.zeros((B,), jnp.int32)
        self.req = jnp.zeros((B,), jnp.int32)
        # host mirrors of the per-slot step inputs: admission/readmission
        # mutate these (free numpy writes) and the round loop uploads them
        # once per dirty round, masked to the slots that actually changed
        self._tok_h = np.zeros((B,), np.int32)
        self._pos_h = np.zeros((B,), np.int32)
        self._req_h = np.zeros((B,), np.int32)
        self._pages_h = np.full((B, maxp), self._dump, np.int32)
        self._dirty = np.zeros((B,), bool)
        self._pages_dirty = np.zeros((B,), bool)
        self.pages = _upload(self._pages_h) if self.paged else None
        # span buckets: each round attends through pages[:, :span] where
        # span is the smallest bucket covering every live slot's writes.
        # Only the page TABLE is sliced (the pool slabs never move), so
        # shrinking the attended span is a free host-side slice for paged
        # mode, while copy-in always attends its full dense [B, max_seq]
        # row. Positions beyond a row's kv_len contribute exact 0.0, so
        # every bucket yields bitwise-identical live-row outputs; buckets
        # are powers of two so the round jit compiles a bounded set of
        # shapes, each lazily on first use.
        bk, b = [], 2
        while b < maxp:
            bk.append(b)
            b *= 2
        self._span_buckets = tuple(bk) + (maxp,)
        self._span_tokens = S   # KV positions a row attends: the last span
        if self.paged:
            self._bind_slabs()
        self.slots: list[_Slot | None] = [None] * B
        step = self.arch.step_factory(cfg, temperature=bscfg.temperature,
                                      seed=bscfg.seed, max_seq=S)
        self._step = jax.jit(step, donate_argnums=(1,))
        sync = bscfg.sync_every

        # the whole round is ONE jitted call: sync_every decode steps
        # scanned on-device, emitting the [slots, sync_every] token chunk —
        # the per-step composition is identical to sync_every separate
        # self._step dispatches (scan runs the same ops in the same order),
        # it just drops the host round-trips between them
        def round_fn(params, caches, tok, pos, req, pages):
            def body(carry, _):
                tok, caches, pos = carry
                tok, caches, pos = step(params, caches, tok, pos, req, pages)
                return (tok, caches, pos), tok
            (tok, caches, pos), toks = jax.lax.scan(
                body, (tok, caches, pos), None, length=sync)
            return tok, caches, pos, jnp.swapaxes(toks[..., 0], 0, 1)

        self._round = jax.jit(round_fn, donate_argnums=(1,))
        # one jitted prefill; jax's jit cache specializes it per shape bucket
        self._prefill = jax.jit(self.arch.prefill_factory(cfg))
        self._pf_caches: dict[tuple[int, int], Any] = {}  # (N, S) -> caches
        if bscfg.prefill_buckets is not None:
            self.buckets = tuple(bscfg.prefill_buckets)
        elif self.arch.prefill_buckets is not None:
            self.buckets = tuple(self.arch.prefill_buckets)
        else:
            self.buckets = tuple(b for b in (2 * T, 4 * T, 8 * T, 16 * T)
                                 if b <= S)
        # batch-N prefill group sizes: powers of two up to prefill_group,
        # so ragged admission batches hit a bounded set of jit shapes
        gs, g = [], 1
        while g < max(1, bscfg.prefill_group):
            gs.append(g)
            g *= 2
        self._group_sizes = tuple(gs) + (max(1, bscfg.prefill_group),)
        self._parked: deque[_Parked] = deque()
        self._sched_skips: dict[int, int] = {}  # uid -> times passed over
        # obs plane (DESIGN.md §13): the metrics registry is engine-owned
        # and always on — counters buffer O(1) host floats, latency
        # histograms bucket host-side, and the F2P fold runs only at
        # sync/export. Tracing is the global opt-in (obs.enable()); every
        # trace site below costs one `is None` probe when disarmed. The old
        # ad-hoc ``self.stats`` dict is now a derived view (property below).
        self.metrics = obs.MetricsRegistry("serve.batched",
                                           seed=bscfg.seed)
        m = self.metrics
        self._c_prefills = m.counter("prefills")
        self._c_prefill_calls = m.counter("prefill_calls")
        self._c_readmits = m.counter("readmits")
        self._c_preempt = m.counter("preemptions")
        self._c_evict = m.counter("host_evictions")
        self._c_rounds = m.counter("rounds")
        self._c_prod = m.counter("productive_slot_steps")
        self._c_emitted = m.counter("emitted_tokens")
        self._g_steps = m.gauge("steps")
        self._g_occ = m.gauge("slot_occupancy")
        self._g_active = m.gauge("slots_active")
        self._h_ttft = m.histogram("ttft_ms", 1e-2, 1e6)
        self._h_tbt = m.histogram("tbt_ms", 1e-3, 1e5)
        self._h_queue = m.histogram("queue_wait_ms", 1e-3, 1e6)
        # per-request wall-clock samples (perf_counter_ns) keyed by uid:
        # visible (first admissible), first_tok; folded into the histograms
        # and per-request trace rows at retirement
        self._rt: dict[int, dict[str, int]] = {}

    # -- stats compatibility view -------------------------------------------
    @property
    def stats(self) -> dict[str, Any]:
        """The pre-obs ad-hoc stats dict, derived from the registry's exact
        shadows. Event keys (prefills/readmits/preemptions/host_evictions)
        appear only once nonzero, matching the old lazy ``.get(k, 0) + 1``
        writes; counts are exact ints, never F2P estimates."""
        d: dict[str, Any] = {
            "steps": int(self._g_steps.value),
            "rounds": self._c_rounds.exact,
            "productive_slot_steps": self._c_prod.exact,
            "emitted_tokens": self._c_emitted.exact,
            "slot_occupancy": self._g_occ.value,
        }
        for key, c in (("prefills", self._c_prefills),
                       ("prefill_calls", self._c_prefill_calls),
                       ("readmits", self._c_readmits),
                       ("preemptions", self._c_preempt),
                       ("host_evictions", self._c_evict)):
            if c.exact:
                d[key] = c.exact
        if self.pool is not None:
            d["pool"] = self.pool.stats()
            d["reserved_pages"] = 1 if self.paged else 0
            d["attn_decode_order"] = self.attn_decode_order
        return d

    # -- slab <-> cache binding (paged decode) ------------------------------
    # The pool slabs ARE the attention caches: the jitted step donates the
    # cache pytree and pool mutations donate slab buffers, so the two homes
    # must always point at the same live QTensors. These host-side pointer
    # updates run at the round boundary (no device work).
    def _bind_slabs(self):
        for key in self.pool.attn_keys:
            self.caches[key] = {kv: self.pool.slabs[key][kv]
                                for kv in ("k", "v")}

    def _push_slabs(self):
        for key in self.pool.attn_keys:
            for kv in ("k", "v"):
                self.pool.slabs[key][kv] = self.caches[key][kv]

    # -- admission ---------------------------------------------------------
    def _bucket_for(self, L: int) -> int:
        for b in self.buckets:
            if L <= b:
                return b
        # longer than every bucket: one-off page-multiple shape
        return -(-L // self.page_tokens) * self.page_tokens

    def _group_size(self, n: int) -> int:
        for g in self._group_sizes:
            if n <= g:
                return g
        return self._group_sizes[-1]

    def _pf_template(self, N: int, S_pf: int):
        caches = self._pf_caches.get((N, S_pf))
        if caches is None:
            caches = init_caches(self.cfg, N, S_pf,
                                 quantized_kv=self.arch.paged_kv,
                                 kv_policy=self.bscfg.kv_policy,
                                 packed_kv=True)
            self._pf_caches[(N, S_pf)] = caches
        return caches

    def _prefill_request(self, prompt: np.ndarray):
        """Run batch-1 prefill; returns (first greedy token [1], pf_caches,
        L). Exact-length for recurrent families, bucket-padded otherwise."""
        L = int(prompt.shape[0])
        T = self.page_tokens
        if self.buckets and self.arch.prefill_buckets is None:
            bucket = self._bucket_for(L)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :L] = prompt
            S_pf = bucket
        else:
            # exact-length prefill (recurrent families): the cache still
            # spans whole pages so the pool can copy page-granular
            toks = np.asarray(prompt, np.int32)[None]
            S_pf = -(-L // T) * T
        if self.arch.recurrent_state:
            # recurrent prefill CONSUMES the cache's initial state — always
            # start from a fresh zero-state cache (never reuse a template a
            # previous admission may alias)
            caches = init_caches(self.cfg, 1, S_pf,
                                 quantized_kv=self.arch.paged_kv,
                                 kv_policy=self.bscfg.kv_policy,
                                 packed_kv=True if self.arch.paged_kv
                                 else None)
        else:
            caches = self._pf_template(1, S_pf)
        logits, pf_caches = self._prefill(
            self.params, jnp.asarray(toks), caches,
            jnp.asarray([L - 1], jnp.int32))
        self._c_prefill_calls.inc()
        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
        return tok0, pf_caches, L

    def _prefill_group(self, prompts: list[np.ndarray], bucket: int):
        """ONE jitted [N, bucket] prefill over compatible prompts (N = the
        next group size, dummy rows zero-padded and ignored). Returns
        (first tokens [n] numpy, pf_caches, lengths). Padding is
        bitwise-invisible: each row's cache and last-token logits depend
        only on that row's own positions (pinned by tests)."""
        with obs.span("prefill.launch"):
            n = len(prompts)
            N = self._group_size(n)
            Ls = [int(p.shape[0]) for p in prompts]
            toks = np.zeros((N, bucket), np.int32)
            last = np.zeros((N,), np.int32)
            for i, p in enumerate(prompts):
                toks[i, :Ls[i]] = p
                last[i] = Ls[i] - 1
            logits, pf_caches = self._prefill(
                self.params, jnp.asarray(toks), self._pf_template(N, bucket),
                jnp.asarray(last, jnp.int32))
            self._c_prefill_calls.inc()
            tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
        with obs.span("prefill.wait"):
            tok0 = np.asarray(tok0)
        return tok0[:n], pf_caches, Ls

    def _copy_recurrent(self, pf_caches, slot: int):
        for i, spec in enumerate(self.cfg.pattern):
            if spec.mixer == "attn":
                continue
            key = f"b{i}"
            self.caches[key] = jax.tree.map(
                lambda full, one: _leaf_set_slot(full, one, jnp.int32(slot)),
                self.caches[key], pf_caches[key])

    def _set_slot_io(self, slot: int, tok0: int, pos: int, uid: int):
        self._tok_h[slot] = tok0
        self._pos_h[slot] = pos
        self._req_h[slot] = uid
        self._dirty[slot] = True

    def _adopt_table(self, slot: int, table: PageTable):
        """Paged admission IS this: the slot takes ownership of the page
        table — a host-side pointer update, no KV copy anywhere."""
        self._tables[slot] = table
        row = self._pages_h[slot]
        row[:] = self._dump
        row[:len(table.pages)] = table.pages
        self._pages_dirty[slot] = True

    def _release_slot(self, slot: int):
        """Retire a paged slot: free its pages, point its table row at the
        dump page so the clamped dead-position writes land in garbage."""
        t = self._tables[slot]
        if t is not None:
            self.pool.free(t.pages)
            self._tables[slot] = None
        self._pages_h[slot] = self._dump
        self._pages_dirty[slot] = True

    def _check_fits(self, r: Request):
        if len(r.tokens) + r.max_new > self.bscfg.max_seq:
            raise ValueError(
                f"request {r.uid}: prompt {len(r.tokens)} + max_new "
                f"{r.max_new} exceeds max_seq {self.bscfg.max_seq}")

    def _place(self, r: Request, slot: int, first: int, L: int,
               table: PageTable | None, results: dict):
        """Common admission tail: adopt/copy KV already handled by caller;
        register slot bookkeeping or early-retire."""
        rt = self._rt[r.uid]
        t1 = time.perf_counter_ns()
        rt["first_tok"] = t1
        self._h_ttft.observe((t1 - rt["visible"]) / 1e6)
        self._set_slot_io(slot, first, L, r.uid)
        self._c_prefills.inc()
        if r.max_new == 1 or (self.bscfg.eos >= 0
                              and first == self.bscfg.eos):
            results[r.uid] = np.asarray([first], np.int32)
            if self.paged and table is not None:
                # retired before adoption: give the prefill pages straight
                # back (the slot's table row still points at the dump page)
                self.pool.free(table.pages)
            self._retire(r.uid, 1)
            return
        if self.paged and table is not None:
            self._adopt_table(slot, table)
        self.slots[slot] = _Slot(uid=r.uid, prompt_len=L, max_new=r.max_new,
                                 tokens=[first])

    def _note_admission(self, r: Request):
        t0 = time.perf_counter_ns()
        rt = self._rt.setdefault(r.uid, {"visible": t0})
        self._h_queue.observe((t0 - rt["visible"]) / 1e6)

    def _admit(self, r: Request, slot: int, results: dict):
        """Batch-1 admission (recurrent families, or a group of one)."""
        self._check_fits(r)
        self._note_admission(r)
        obs.instant("admit", uid=r.uid, slot=slot)
        with obs.span("prefill", uid=r.uid, L=len(r.tokens)):
            with obs.span("prefill.launch"):
                tok0, pf_caches, L = self._prefill_request(
                    np.asarray(r.tokens))
            table = None
            with obs.span("prefill.store"):
                if self.pool is not None:
                    table = self.pool.store_prefill(pf_caches, L)
                    if not self.paged:
                        self.caches = self.pool.load_into_slot(
                            table, self.caches, slot)
                        self.pool.free(table.pages)
                        table = None
                if self.arch.recurrent_state:
                    self._copy_recurrent(pf_caches, slot)
            # first token: argmax of the prefill logits, same as the
            # sequential engine — it is token 0 of the output
            with obs.span("prefill.wait"):
                first = int(np.asarray(tok0)[0])
        self._place(r, slot, first, L, table, results)

    def _admit_batch(self, pairs: list[tuple[Request, int]], results: dict):
        """Admit requests into slots, fusing compatible prompts into
        bucketed batch-N prefill calls (ROADMAP item 1 headroom retired)."""
        for r, _ in pairs:
            self._check_fits(r)
        if (self.arch.recurrent_state or self.bscfg.prefill_group <= 1
                or not self.buckets or self.arch.prefill_buckets is not None
                or self.pool is None):
            for r, s in pairs:
                self._admit(r, s, results)
            return
        by_bucket: dict[int, list[tuple[Request, int]]] = {}
        for r, s in pairs:
            by_bucket.setdefault(self._bucket_for(len(r.tokens)),
                                 []).append((r, s))
        cap = max(1, self.bscfg.prefill_group)
        for bucket in sorted(by_bucket):
            grp = by_bucket[bucket]
            while grp:
                chunk, grp = grp[:cap], grp[cap:]
                if len(chunk) == 1:
                    self._admit(*chunk[0], results)
                    continue
                self._admit_group(chunk, bucket, results)

    def _admit_group(self, chunk: list[tuple[Request, int]], bucket: int,
                     results: dict):
        for r, s in chunk:
            self._note_admission(r)
            obs.instant("admit", uid=r.uid, slot=s)
        with obs.span("prefill_group", n=len(chunk), bucket=bucket):
            tok0, pf_caches, Ls = self._prefill_group(
                [np.asarray(r.tokens) for r, _ in chunk], bucket)
            with obs.span("prefill.store"):
                for i, (r, s) in enumerate(chunk):
                    table = self.pool.store_prefill(pf_caches, Ls[i], row=i)
                    if not self.paged:
                        self.caches = self.pool.load_into_slot(
                            table, self.caches, s)
                        self.pool.free(table.pages)
                        table = None
                    self._place(r, s, int(tok0[i]), Ls[i], table, results)

    def _retire(self, uid: int, n_tokens: int):
        """Fold a finished request's timing into the histograms and (when
        tracing is armed) emit its per-request trace row: a ``ttft`` span
        from first visibility to the prefill token and a ``decode`` span
        from first token to retirement carrying the mean TBT."""
        rt = self._rt.pop(uid, None)
        self._sched_skips.pop(uid, None)
        if rt is None:
            return
        now = time.perf_counter_ns()
        ft = rt.get("first_tok", now)
        tbt_ms = ((now - ft) / 1e6) / (n_tokens - 1) if n_tokens > 1 else 0.0
        if n_tokens > 1:
            self._h_tbt.observe(tbt_ms)
        s = obs.get()
        if s is None or s.tracer is None:
            return
        tr = s.tracer
        tid = uid + 1                       # row per request; engine row = 0
        tr.thread_name(tid, f"req {uid}")
        tr.complete("ttft", tr.ts_of(rt["visible"]),
                    (ft - rt["visible"]) / 1e3, tid=tid, uid=uid)
        tr.complete("decode", tr.ts_of(ft), (now - ft) / 1e3, tid=tid,
                    uid=uid, tokens=n_tokens, tbt_ms=round(tbt_ms, 4))
        tr.instant("retire", uid=uid)

    def _readmit(self, p: _Parked, slot: int):
        if self.pool is not None:
            table = p.table if p.table is not None \
                else self.pool.restore_from_host(p.host)
            if self.paged:
                self._adopt_table(slot, table)
            else:
                self.caches = self.pool.load_into_slot(table, self.caches,
                                                       slot)
                self.pool.free(table.pages)
        if p.state is not None:
            for key, blob in p.state.items():
                self.caches[key] = jax.tree.map(
                    lambda full, one: _leaf_set_slot(
                        full, jnp.asarray(one), jnp.int32(slot)),
                    self.caches[key], blob)
        self._set_slot_io(slot, int(p.last_tok), p.pos, p.uid)
        self.slots[slot] = _Slot(uid=p.uid, prompt_len=p.prompt_len,
                                 max_new=p.max_new, tokens=p.tokens)
        self._c_readmits.inc()
        obs.instant("readmit", uid=p.uid, slot=slot, pos=p.pos)

    # -- preemption --------------------------------------------------------
    def _park_slot(self, slot: int) -> _Parked:
        st = self.slots[slot]
        pos = st.prompt_len + len(st.tokens) - 1   # next write position
        parked = _Parked(uid=st.uid, prompt_len=st.prompt_len,
                         max_new=st.max_new, tokens=st.tokens, pos=pos,
                         last_tok=st.tokens[-1])
        if self.pool is not None:
            if self.paged:
                # the live pages ARE the request's KV: hand the table over,
                # trimming look-ahead growth pages beyond the live length
                table = self._tables[slot]
                self._tables[slot] = None
                self.pool.trim(table, pos)
                parked.table = table
                self._pages_h[slot] = self._dump
                self._pages_dirty[slot] = True
            else:
                parked.table = self.pool.store_from_slot(self.caches, slot,
                                                         pos)
            if self.bscfg.evict_parked_to_host:
                parked.host = self.pool.evict_to_host(parked.table)
                parked.table = None
                self._c_evict.inc()
                obs.instant("evict", uid=st.uid, slot=slot)
        if self.arch.recurrent_state:
            parked.state = {}
            for i, spec in enumerate(self.cfg.pattern):
                if spec.mixer == "attn":
                    continue
                key = f"b{i}"
                parked.state[key] = jax.tree.map(
                    lambda leaf: np.asarray(leaf[:, slot:slot + 1]),
                    self.caches[key])
        self.slots[slot] = None
        self._c_preempt.inc()
        obs.instant("preempt", uid=st.uid, slot=slot, pos=pos)
        return parked

    def preempt(self, uid: int) -> _Parked:
        """Forcibly park the slot serving ``uid`` (test/chaos hook)."""
        for s, st in enumerate(self.slots):
            if st is not None and st.uid == uid:
                p = self._park_slot(s)
                self._parked.append(p)
                return p
        raise KeyError(f"request {uid} not active")

    # -- pool maintenance (paged) ------------------------------------------
    def _grow_tables(self) -> int:
        """Lazy page growth: before each round, extend every live table to
        cover the positions this round will write (pos .. pos+sync_every-1,
        clamped like the device). Slot KV stays page-granular in live
        length instead of pre-committing max_seq — which is also the fast
        shape: dead table entries keep pointing at the (cache-hot) dump
        page, so the kernel's full-span gather streams only live pages.

        Returns the max page count any live slot needs this round — the
        round's attended span (``_rounds`` buckets it). Retired rows are
        excluded on purpose: their clamped dead-position writes land via
        an index that XLA clamps into the sliced table's last column,
        which for a released row points at the dump page, and their
        outputs are discarded at harvest."""
        S, T = self.bscfg.max_seq, self.page_tokens
        maxp = S // T
        need_max = 1
        for s, st in enumerate(self.slots):
            if st is None:
                continue
            pos = st.prompt_len + len(st.tokens) - 1
            end = min(pos + self.bscfg.sync_every - 1, S - 1)
            need = min(end // T + 1, maxp)
            need_max = max(need_max, need)
            t = self._tables[s]
            if need > len(t.pages):
                have = len(t.pages)
                new = self.pool.extend(t, need - have)
                self._pages_h[s, have:need] = new
                self._pages_dirty[s] = True
        return need_max

    def relocate_slot(self, slot: int):
        """Move a live slot's pages to fresh pool slots mid-decode
        (defrag/chaos hook) — a whole-word copy, bitwise-invisible."""
        if not self.paged or self._tables[slot] is None:
            return
        t = self.pool.relocate(self._tables[slot])
        self._tables[slot] = t
        self._pages_h[slot, :len(t.pages)] = t.pages
        self._pages_dirty[slot] = True

    def compact_pool(self):
        """Defragment the pool under every live owner: the dump page first
        (pinning it at page 0), then live slot tables, then parked tables.
        Word-granular moves; updates the device page tables next round."""
        if not self.paged:
            return
        dump_t = PageTable(pages=[self._dump], length=0)
        live = [(s, t) for s, t in enumerate(self._tables) if t is not None]
        tables = [dump_t] + [t for _, t in live] \
            + [p.table for p in self._parked if p.table is not None]
        self.pool.compact(tables)
        self._dump = dump_t.pages[0]
        for s, t in live:
            self._pages_h[s, :len(t.pages)] = t.pages
            self._pages_h[s, len(t.pages):] = self._dump
        for s in range(self.bscfg.slots):
            if self._tables[s] is None:
                self._pages_h[s] = self._dump
        self._pages_dirty[:] = True

    # -- the run loop ------------------------------------------------------
    def _n_active(self) -> int:
        return sum(st is not None for st in self.slots)

    def _free_slots(self):
        return [s for s, st in enumerate(self.slots) if st is None]

    def _upload_io(self):
        io, pg = self._dirty, self._pages_dirty
        pg_any = self.paged and pg.any()
        if not (io.any() or pg_any):
            return
        if self.bscfg.io_upload == "full":
            self.tok = _upload(self._tok_h[:, None])
            self.pos = _upload(self._pos_h)
            self.req = _upload(self._req_h)
            if self.paged:
                self.pages = _upload(self._pages_h)
        else:
            # token/pos/req rows dirty only at admission boundaries; page
            # rows also go dirty every growth round — two masks, so the
            # steady decode round uploads ONE small [slots, max_pages] delta
            if io.any():
                self.tok, self.pos, self.req = _io_delta(
                    self.tok, self.pos, self.req, _upload(io),
                    _upload(self._tok_h), _upload(self._pos_h),
                    _upload(self._req_h))
            if pg_any:
                self.pages = _pages_delta(self.pages, _upload(pg),
                                          _upload(self._pages_h))
        io[:] = False
        pg[:] = False

    def _rounds(self) -> np.ndarray:
        """``sync_every`` decode steps; one [slots, sync_every] host sync."""
        with obs.span("round.prep"):
            need = 0
            if self.paged:
                need = self._grow_tables()
                self._bind_slabs()  # pool ops may have rebuilt slab buffers
            self._upload_io()
            pages = self.pages
            if self.paged:
                # attend only the live span: slice the page TABLE to the
                # smallest bucket covering every live slot (the KV slabs
                # never move, so this is one tiny device slice). Copy-in has
                # no such lever — its dense cache row is [slots, max_seq].
                span = next((b for b in self._span_buckets if b >= need),
                            self._span_buckets[-1])
                self._span_tokens = span * self.page_tokens
                if span < pages.shape[1]:
                    pages = pages[:, :span]
        with obs.span("round.launch"):
            self.tok, self.caches, self.pos, chunk_d = self._round(
                self.params, self.caches, self.tok, self.pos, self.req,
                pages)
            if self.paged:
                self._push_slabs()  # the round donated+rebuilt the slabs
        with obs.span("round.wait"):
            chunk = np.asarray(chunk_d)
            # keep the mirrors in lockstep: last emitted token is the next
            # step input; position advances one per step, clamped exactly
            # like the device-side jnp.minimum(pos + 1, max_seq - 1)
            self._tok_h[:] = chunk[:, -1]
            np.minimum(self._pos_h + self.bscfg.sync_every,
                       self.bscfg.max_seq - 1, out=self._pos_h)
        return chunk

    def _kept_work(self, chunk: np.ndarray) -> dict[str, int]:
        """What the round just synced did for the tokens harvest will keep
        (the same stopping rule as :meth:`_harvest`): ``kv_live``, the
        context each kept token's step attended, summed; ``kv_written``,
        the kept tokens (one KV row written each); ``steps_kept``, the
        round's steps that kept a token for some live request;
        ``kv_attended``, the KV positions those steps' attention covered:
        every slot row, live or not, over the round's span, per kept
        step."""
        live = written = steps = 0
        eos = self.bscfg.eos
        for s, st in enumerate(self.slots):
            if st is None:
                continue
            n = min(chunk.shape[1], st.max_new - len(st.tokens))
            if eos >= 0:
                hit = np.flatnonzero(chunk[s, :n] == eos)
                if hit.size:
                    n = int(hit[0]) + 1
            # step k reads the token at position prompt_len + have - 1 + k
            # and attends every position up to it
            ctx0 = st.prompt_len + len(st.tokens)
            live += n * ctx0 + n * (n - 1) // 2
            written += n
            steps = max(steps, n)
        return {"kv_live": live, "kv_written": written,
                "steps_kept": steps,
                "kv_attended": self.bscfg.slots * self._span_tokens * steps}

    def _harvest(self, chunk: np.ndarray, results: dict):
        for s, st in enumerate(self.slots):
            if st is None:
                continue
            for k in range(chunk.shape[1]):
                t = int(chunk[s, k])
                st.tokens.append(t)
                done = len(st.tokens) >= st.max_new or \
                    (self.bscfg.eos >= 0 and t == self.bscfg.eos)
                if done:
                    results[st.uid] = np.asarray(st.tokens[:st.max_new],
                                                 np.int32)
                    self.slots[s] = None
                    if self.paged:
                        self._release_slot(s)
                    self._retire(st.uid, len(results[st.uid]))
                    break

    # -- latency-aware admission (DESIGN.md §14) ---------------------------
    def _select_admissions(self, pending: list[Request], step_no: int,
                           k: int) -> list[Request]:
        """Pick up to ``k`` admissible requests. ``scheduler="slo"`` scores
        queue-wait age (normalized by min(slo_ttft_ms, observed p50 from the
        obs queue-wait histogram)) minus a projected-decode-tail penalty:
        aging requests dominate under pressure, short-tail requests jump
        ahead under light load. A request passed over ``preempt_patience``
        times scores +inf — the FIFO starvation bound as a hard floor."""
        adm = [r for r in pending if r.arrival <= step_no]
        if not adm or k <= 0:
            return []
        if self.bscfg.scheduler == "fifo" or len(adm) <= k:
            chosen = adm[:k]
        else:
            now = time.perf_counter_ns()
            slo = max(float(self.bscfg.slo_ttft_ms), 1e-3)
            try:
                q50 = float(self._h_queue.quantile(0.5, exact=True))
            except Exception:
                q50 = 0.0
            norm = min(slo, q50) if np.isfinite(q50) and q50 > 0 else slo
            floor = max(1, self.bscfg.preempt_patience)

            def score(r: Request) -> float:
                if self._sched_skips.get(r.uid, 0) >= floor:
                    return float("inf")
                vis = self._rt.get(r.uid, {}).get("visible", now)
                age_ms = (now - vis) / 1e6
                return (age_ms / norm - self.bscfg.sched_tail_weight
                        * r.max_new / self.bscfg.max_seq)

            ranked = sorted(adm, key=lambda r: (-score(r), r.arrival, r.uid))
            chosen = ranked[:k]
        taken = {r.uid for r in chosen}
        for r in adm:
            if r.uid not in taken:
                self._sched_skips[r.uid] = \
                    self._sched_skips.get(r.uid, 0) + 1
        pending[:] = [r for r in pending if r.uid not in taken]
        return chosen

    def run(self, requests: list[Request]) -> dict[int, np.ndarray]:
        self.metrics.reset()
        self._rt = {}
        self._sched_skips = {}
        pending = sorted(requests, key=lambda r: (r.arrival, r.uid))
        self._parked = deque()
        parked = self._parked
        results: dict[int, np.ndarray] = {}
        step_no = 0
        starve_rounds = 0
        tracing = obs.get() is not None and obs.get().tracer is not None
        if tracing:
            obs.get().tracer.thread_name(0, "engine")
        while pending or parked or self._n_active():
            with obs.span("schedule"):
                # stamp first-visibility time on newly admissible requests
                # (the queue-wait/TTFT clock starts when a request COULD be
                # admitted)
                now = time.perf_counter_ns()
                for r in pending:
                    if r.arrival > step_no:
                        break
                    self._rt.setdefault(r.uid, {"visible": now})
                # admit: parked first (they hold evicted state), then
                # arrivals picked by the SLO scheduler and batch-prefilled
                # per bucket
                new_slots = []
                for s in self._free_slots():
                    if parked:
                        self._readmit(parked.popleft(), s)
                    else:
                        new_slots.append(s)
                if new_slots and pending:
                    chosen = self._select_admissions(pending, step_no,
                                                     len(new_slots))
                    if chosen:
                        self._admit_batch(list(zip(chosen, new_slots)),
                                          results)
            if not self._n_active():
                # idle: fast-forward the clock to the next arrival
                if pending:
                    step_no = max(step_no, pending[0].arrival)
                    continue
                break   # only parked left with no free slot: impossible
            with obs.span("round", step=step_no):
                chunk = self._rounds()
            with obs.span("harvest"):
                n_act = self._n_active()
                step_no += self.bscfg.sync_every
                self._g_steps.set(step_no)
                self._g_active.set(n_act)
                self._c_rounds.inc()
                self._c_prod.inc(n_act * self.bscfg.sync_every)
                if tracing:
                    series = {"active": n_act}
                    if self.pool is not None:
                        series["pool_used"] = self.pool.used
                    series.update(self._kept_work(chunk))
                    obs.counter_event("slots", **series)
                before = len(results)
                self._harvest(chunk, results)
                if self.bscfg.defrag_every and \
                        self._c_rounds.exact % self.bscfg.defrag_every == 0:
                    self.compact_pool()
                # starvation -> preempt the longest-remaining-tail slot and
                # admit the scheduler's pick
                waiting = (any(r.arrival <= step_no for r in pending)
                           and not self._free_slots())
                retired = len(results) > before
                starve_rounds = starve_rounds + 1 \
                    if (waiting and not retired) else 0
                if waiting and starve_rounds >= self.bscfg.preempt_patience:
                    victim = max(
                        (s for s, st in enumerate(self.slots)
                         if st is not None),
                        key=lambda s: self.slots[s].max_new
                        - len(self.slots[s].tokens))
                    parked.append(self._park_slot(victim))
                    chosen = self._select_admissions(pending, step_no, 1)
                    if chosen:
                        self._admit_batch([(chosen[0], victim)], results)
                    starve_rounds = 0
        # flush any unfinished (shouldn't happen: harvest retires at max_new)
        for s, st in enumerate(self.slots):
            if st is not None:
                results[st.uid] = np.asarray(st.tokens[:st.max_new],
                                             np.int32)
                if self.paged:
                    self._release_slot(s)
                self._retire(st.uid, len(results[st.uid]))
        self.slots = [None] * self.bscfg.slots
        total = sum(len(v) for v in results.values())
        self._c_emitted.inc(total)
        denom = self.bscfg.slots * self._c_rounds.exact \
            * self.bscfg.sync_every
        self._g_occ.set(self._c_prod.exact / denom if denom else 0.0)
        return results
