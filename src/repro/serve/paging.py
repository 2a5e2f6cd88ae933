"""Block-paged pool of packed-F2P KV slabs (DESIGN.md §12).

The pool owns, per attention position in ``cfg.pattern`` and per k/v, one
**slab**: a packed :class:`~repro.core.qtensor.QTensor` of logical shape
``[G, n_pages, page_tokens, K*hd]`` blocked over ``hd`` — each token's kv
heads side by side in one row, so the codes leaf ``[..., K*words]`` is
lane-dense on the TPU (a ``[..., K, words]`` leaf with 32-word rows would
be padded 4x in HBM). The word image is the per-head ``[..., K, words]``
cache row's, reshaped: hd*n_bits is a multiple of 32, so per-head rows
concatenate into the packed K*hd row bit for bit. A logical *page* is one
index on the
page axis — the same index across every slab — holding ``page_tokens``
consecutive cache positions of every layer at once, so a request's KV is
described by a single ordered page list (:class:`PageTable`) plus its live
length.

Word alignment is by construction, not by arithmetic: the packed cache
layout (DESIGN.md §9) blocks over head_dim, so every token's codes occupy
whole uint32 words (``packed_words(head_dim, n_bits)`` per (token, kv-head))
and a page boundary can never split a word. Every pool operation below is
therefore a pure word copy — ``gather``/``scatter`` of uint32 code words and
f32 scales with **zero repack** — which is what makes pages relocatable
bit-exactly (pinned by tests/test_serve_batched.py across n_bits 6/8/16).

All slab mutations run through tiny jitted helpers with the destination
buffer donated, so steady-state paging does not re-allocate the pool.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qtensor import QTensor
from repro.models import attention as A
from repro.models.config import ModelConfig


class PoolExhausted(RuntimeError):
    """Raised when an allocation needs more free pages than the pool has."""


@dataclasses.dataclass
class PageTable:
    """One request's view into the pool: ordered page ids + live length."""
    pages: list[int]
    length: int


@dataclasses.dataclass
class HostKV:
    """A request's KV evicted to host memory (numpy), page-granular."""
    data: dict[str, dict[str, tuple[np.ndarray, np.ndarray]]]
    length: int


# --- jitted slab primitives (destination donated; shapes specialize) -------
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(slab, pages, blocks):
    """slab [G,P,T,...] <- blocks [G,n,T,...] at page ids ``pages`` [n]."""
    return slab.at[:, pages].set(blocks)


@jax.jit
def _gather_pages(slab, pages):
    return jnp.take(slab, pages, axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _store_row_all(slab_parts, cache_parts, pages, row):
    """Every slab leaf <- pages of cache row ``row``, ONE jitted dispatch.

    ``slab_parts``/``cache_parts`` are parallel plain-dict pytrees of raw
    codes/scales arrays (QTensor aux differs between slab and cache shapes,
    so the QTensors themselves can't be tree-mapped against each other).
    Admission runs this once per request — per-leaf dispatch overhead was
    the dominant cost of the paged admission path on CPU."""
    n = pages.shape[0]

    def one(slab, leaf):
        G, T = slab.shape[0], slab.shape[2]
        size = (G, 1, n * T) + leaf.shape[3:]
        start = (jnp.int32(0), row) + (jnp.int32(0),) * (leaf.ndim - 2)
        blk = jax.lax.dynamic_slice(leaf, start, size).reshape(
            (G, n, T) + slab.shape[3:])
        return slab.at[:, pages].set(blk)

    return jax.tree.map(one, slab_parts, cache_parts)


@functools.partial(jax.jit, donate_argnums=(1,))
def _load_row_all(slab_parts, cache_parts, pages, row):
    """Cache row ``row`` <- gathered pages, every leaf in ONE dispatch
    (cache buffers donated — the engine rebinds its cache pytree)."""
    n = pages.shape[0]

    def one(slab, leaf):
        G, T = slab.shape[0], slab.shape[2]
        blk = jnp.take(slab, pages, axis=1).reshape(
            (G, 1, n * T) + leaf.shape[3:])
        start = (jnp.int32(0), row) + (jnp.int32(0),) * (leaf.ndim - 2)
        return jax.lax.dynamic_update_slice(leaf, blk, start)

    return jax.tree.map(one, slab_parts, cache_parts)


@functools.partial(jax.jit, donate_argnums=(0,))
def _move_pages_all(slab_parts, src, dst):
    """Relocate pages src -> dst across every slab leaf in one dispatch
    (overlap-safe: the gather reads before the scatter writes)."""
    return jax.tree.map(
        lambda s: s.at[:, dst].set(jnp.take(s, src, axis=1)), slab_parts)


class PagedKVPool:
    """Fixed-capacity paged store for the packed KV of a model's attention
    layers. Pages move between three homes with bit-exact word copies:

    * a **slot row** of the engine's decode cache (``load_into_slot`` /
      ``store_from_slot``),
    * the **pool slabs** themselves (``store_prefill``, ``relocate``,
      ``compact``),
    * **host memory** (``evict_to_host`` / ``restore_from_host``).
    """

    def __init__(self, cfg: ModelConfig, page_tokens: int, n_pages: int, *,
                 kv_policy: Any = None):
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.cfg = cfg
        self.page_tokens = int(page_tokens)
        self.n_pages = int(n_pages)
        self._free = list(range(n_pages))[::-1]   # stack: pop() = lowest last
        self._free_set = set(self._free)   # membership: free() scans nothing
        self.peak_used = 0
        G, K, hd = cfg.n_groups, cfg.n_kv_heads, cfg.head_dim
        self.attn_keys = [f"b{i}" for i, s in enumerate(cfg.pattern)
                          if s.mixer == "attn"]
        from repro.kernels.bits import pack_bits_np

        self.slabs: dict[str, dict[str, QTensor]] = {}
        for key in self.attn_keys:
            fmt = A.KV_FMT
            if kv_policy is not None:
                fmt, _ = kv_policy.f2p_for(f"kv/{key}", (fmt, 0))
            zero_code = int(fmt.encode_nearest(np.zeros(1))[0])
            if hd * fmt.n_bits % 32:
                raise ValueError(
                    f"head_dim {hd} x {fmt.n_bits} bits is not whole words")
            row = pack_bits_np(np.full((K * hd,), zero_code, np.uint32),
                               fmt.n_bits)
            shape = (G, n_pages, page_tokens, K * hd)
            # one MATERIALIZED buffer per (k/v, leaf): slab ops donate their
            # buffers, so k and v must never alias the same storage
            self.slabs[key] = {
                kv: QTensor.from_parts(
                    jnp.tile(jnp.asarray(row), (G, n_pages, page_tokens, 1)),
                    jnp.ones((G, n_pages, page_tokens, K), jnp.float32),
                    fmt, hd, shape, packed=True)
                for kv in ("k", "v")}

    # -- allocation --------------------------------------------------------
    def pages_for(self, length: int) -> int:
        return -(-int(length) // self.page_tokens)

    @property
    def used(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)}/{self.n_pages} free")
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        self.peak_used = max(self.peak_used, self.used)
        return pages

    def free(self, pages: list[int]) -> None:
        freed: set[int] = set()
        for p in pages:
            if not 0 <= p < self.n_pages or p in self._free_set or p in freed:
                raise ValueError(f"bad free of page {p}")
            freed.add(p)
        self._free.extend(sorted(pages, reverse=True))
        self._free_set |= freed

    def extend(self, table: PageTable, n: int) -> list[int]:
        """Grow a live table by ``n`` fresh pages (paged decode's lazy
        growth: the engine appends pages just ahead of the write position,
        so a request only ever owns pages covering tokens it will actually
        write this round)."""
        new = self.alloc(n)
        table.pages.extend(new)
        return new

    def trim(self, table: PageTable, length: int) -> None:
        """Shrink a table to the pages covering ``length`` tokens, freeing
        look-ahead growth pages beyond them, and record the live length
        (park/evict keep only live KV)."""
        keep = self.pages_for(length)
        if keep < len(table.pages):
            self.free(table.pages[keep:])
            del table.pages[keep:]
        table.length = int(length)

    # -- page <-> slab movement -------------------------------------------
    def _each_leaf(self):
        for key in self.attn_keys:
            for kv in ("k", "v"):
                yield key, kv

    def _update_slab(self, key, kv, codes, scales):
        qt = self.slabs[key][kv]
        self.slabs[key][kv] = QTensor.from_parts(
            codes, scales, qt.fmt, qt.block, qt.shape, packed=qt.packed)

    def _slab_parts(self):
        """Plain-dict pytree of the raw slab codes/scales arrays (the fused
        jitted ops tree-map these against same-structure cache parts)."""
        return {key: {kv: {"codes": self.slabs[key][kv].codes,
                           "scales": self.slabs[key][kv].scales}
                      for kv in ("k", "v")} for key in self.attn_keys}

    def _cache_parts(self, caches):
        parts = {}
        for key in self.attn_keys:
            parts[key] = {}
            for kv in ("k", "v"):
                qt = caches[key][kv]
                if not (isinstance(qt, QTensor) and qt.packed):
                    raise TypeError(
                        f"cache {key}/{kv} must be a packed QTensor")
                parts[key][kv] = {"codes": qt.codes, "scales": qt.scales}
        return parts

    def _rebind_slabs(self, parts):
        for key, kv in self._each_leaf():
            self._update_slab(key, kv, parts[key][kv]["codes"],
                              parts[key][kv]["scales"])

    def store_prefill(self, caches, length: int, row: int = 0) -> PageTable:
        """Copy row ``row`` of a prefill cache pytree into fresh pages.
        The cache's token axis must cover ceil(length / page_tokens) pages
        (bucketed prefill caches are sized in whole pages)."""
        return self._store_row(caches, length, row)

    def store_from_slot(self, caches, slot: int, length: int) -> PageTable:
        """Page out a live decode-cache slot (preemption)."""
        return self._store_row(caches, length, slot)

    def _store_row(self, caches, length: int, row: int) -> PageTable:
        n = self.pages_for(length)
        pages = self.alloc(n)
        idx = jnp.asarray(pages, jnp.int32)
        self._rebind_slabs(_store_row_all(
            self._slab_parts(), self._cache_parts(caches), idx,
            jnp.int32(row)))
        return PageTable(pages=pages, length=int(length))

    def load_into_slot(self, table: PageTable, caches, slot: int):
        """Copy a page table's KV into row ``slot`` of the decode cache
        pytree; returns the updated pytree (cache leaves donated)."""
        idx = jnp.asarray(table.pages, jnp.int32)
        parts = _load_row_all(self._slab_parts(), self._cache_parts(caches),
                              idx, jnp.int32(slot))
        out = dict(caches)
        for key in self.attn_keys:
            ent = dict(out[key])
            for kv in ("k", "v"):
                qt = ent[kv]
                ent[kv] = QTensor.from_parts(
                    parts[key][kv]["codes"], parts[key][kv]["scales"],
                    qt.fmt, qt.block, qt.shape, packed=qt.packed)
            out[key] = ent
        return out

    # -- relocation / defrag ----------------------------------------------
    def relocate(self, table: PageTable) -> PageTable:
        """Move a request's pages to fresh slots (alloc-copy-free). The copy
        is whole uint32 words — bit-exact by construction."""
        new = self.alloc(len(table.pages))
        src = jnp.asarray(table.pages, jnp.int32)
        dst = jnp.asarray(new, jnp.int32)
        self._rebind_slabs(_move_pages_all(self._slab_parts(), src, dst))
        self.free(table.pages)
        return PageTable(pages=new, length=table.length)

    def compact(self, tables: list[PageTable]) -> None:
        """Defragment: repack every live page into the lowest slots, in table
        order, updating the tables in place. One gather-then-scatter per
        slab leaf."""
        src, dst = [], []
        nxt = 0
        for t in tables:
            newpages = []
            for p in t.pages:
                if p != nxt:
                    src.append(p)
                    dst.append(nxt)
                newpages.append(nxt)
                nxt += 1
            t.pages = newpages
        if src:
            s = jnp.asarray(src, jnp.int32)
            d = jnp.asarray(dst, jnp.int32)
            self._rebind_slabs(_move_pages_all(self._slab_parts(), s, d))
        self._free = list(range(nxt, self.n_pages))[::-1]
        self._free_set = set(self._free)

    # -- host eviction -----------------------------------------------------
    def evict_to_host(self, table: PageTable) -> HostKV:
        """Pull a page table's contents to host numpy and free its pages."""
        idx = jnp.asarray(table.pages, jnp.int32)
        data: dict[str, dict[str, tuple[np.ndarray, np.ndarray]]] = {}
        for key in self.attn_keys:
            data[key] = {}
            for kv in ("k", "v"):
                slab = self.slabs[key][kv]
                data[key][kv] = (np.asarray(_gather_pages(slab.codes, idx)),
                                 np.asarray(_gather_pages(slab.scales, idx)))
        self.free(table.pages)
        return HostKV(data=data, length=table.length)

    def restore_from_host(self, host: HostKV) -> PageTable:
        """Upload host-evicted KV into fresh pages."""
        n = self.pages_for(host.length)
        pages = self.alloc(n)
        idx = jnp.asarray(pages, jnp.int32)
        for key, kv in self._each_leaf():
            slab = self.slabs[key][kv]
            codes_h, scales_h = host.data[key][kv]
            self._update_slab(
                key, kv,
                _scatter_pages(slab.codes, idx, jnp.asarray(codes_h)),
                _scatter_pages(slab.scales, idx, jnp.asarray(scales_h)))
        return PageTable(pages=pages, length=host.length)

    # -- accounting --------------------------------------------------------
    def occupancy(self) -> float:
        return self.used / self.n_pages

    def page_bytes_packed(self) -> int:
        """Packed bytes of ONE logical page across every slab — word-granular
        through the canonical ``packed_nbytes`` (QTensor.nbytes) accounting."""
        total = 0
        for key, kv in self._each_leaf():
            total += self.slabs[key][kv].nbytes
        return total // self.n_pages

    def pool_bytes_packed(self) -> int:
        return sum(self.slabs[k][kv].nbytes for k, kv in self._each_leaf())

    def pool_bytes_live_packed(self) -> int:
        """Packed bytes of the ALLOCATED pages only — with paged decode this
        IS the resident KV footprint (slot KV scales with live tokens at
        page granularity, not with slots * max_seq)."""
        return self.used * self.page_bytes_packed()

    def pool_bytes_logical_f32(self) -> int:
        """What the same pool would weigh holding dense f32 KV."""
        total = 0
        for key, kv in self._each_leaf():
            total += int(np.prod(self.slabs[key][kv].shape)) * 4
        return total

    def stats(self) -> dict:
        return {
            "n_pages": self.n_pages,
            "used": self.used,
            "peak_used": self.peak_used,
            "occupancy": self.occupancy(),
            "page_tokens": self.page_tokens,
            "page_bytes_packed": self.page_bytes_packed(),
            "pool_bytes_packed": self.pool_bytes_packed(),
            "pool_bytes_live_packed": self.pool_bytes_live_packed(),
            "pool_bytes_logical_f32": self.pool_bytes_logical_f32(),
        }
