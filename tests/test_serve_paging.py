"""The paged pool's free list (serve/paging.py): ``free`` tests membership
in a set kept beside the LIFO stack instead of scanning the stack, and
hands out the same pages in the same order as the list scan did, so page
assignment and every served token are unchanged."""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import (BatchedEngine, BatchedServeConfig, PagedKVPool,
                         Request)
from repro.serve.paging import PageTable


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("llama3_2_3b")


class ListScanFree:
    """The stack as it was kept before: ``free`` scans the list of free
    pages for each page freed. The reference the pool must match."""

    def __init__(self, n_pages):
        self.n_pages = n_pages
        self.stack = list(range(n_pages))[::-1]

    def alloc(self, n):
        return [self.stack.pop() for _ in range(n)]

    def free(self, pages):
        for p in pages:
            if not 0 <= p < self.n_pages or p in self.stack:
                raise ValueError(f"bad free of page {p}")
        self.stack.extend(sorted(pages, reverse=True))

    def compact(self, tables):
        self.stack = list(range(sum(map(len, tables)), self.n_pages))[::-1]


def test_pages_handed_out_match_the_recorded_sequence(cfg):
    """Pages of a fixed script of allocations, frees and a compaction, as
    the list-scan pool handed them out (recorded before the change)."""
    pool = PagedKVPool(cfg, 8, 16)
    log = []
    a, b = pool.alloc(3), pool.alloc(4)
    log += [a, b]
    pool.free([a[1], a[0]])
    log.append(c := pool.alloc(3))
    pool.free(b)
    log.append(d := pool.alloc(5))
    t1, t2 = PageTable(c + [a[2]], 0), PageTable(d, 0)
    pool.compact([t2, t1])
    log += [t2.pages, t1.pages]
    log.append(pool.alloc(2))
    pool.free(t1.pages[::2])
    log.append(pool.alloc(4))
    assert log == [[0, 1, 2], [3, 4, 5, 6], [0, 1, 7], [3, 4, 5, 6, 8],
                   [0, 1, 2, 3, 4], [5, 6, 7, 8], [9, 10], [5, 7, 11, 12]]
    assert pool._free == [15, 14, 13]
    assert pool.used == 13


def test_alloc_free_compact_follow_the_list_scan(cfg):
    """A long seeded run of allocations, partial and whole frees and
    compactions: the same pages in the same order as the reference."""
    n = 64
    pool, ref = PagedKVPool(cfg, 8, n), ListScanFree(n)
    rng = np.random.default_rng(0)
    tables: list[list[int]] = []
    for _ in range(400):
        op = rng.integers(0, 10)
        if op < 5 and pool.used < n - 8:
            k = int(rng.integers(1, 9))
            got = pool.alloc(k)
            assert got == ref.alloc(k)
            tables.append(got)
        elif op < 9 and tables:
            t = tables[int(rng.integers(len(tables)))]
            cut = int(rng.integers(0, len(t)))  # a trim, or the whole table
            pool.free(t[cut:])
            ref.free(t[cut:])
            del t[cut:]
            tables = [t for t in tables if t]
        else:
            pts = [PageTable(list(t), 0) for t in tables]
            pool.compact(pts)
            ref.compact(tables)
            tables = [t.pages for t in pts]
        assert pool._free == ref.stack
        assert pool.used == n - len(ref.stack)


def test_bad_frees_raise_and_change_nothing(cfg):
    pool = PagedKVPool(cfg, 8, 8)
    pages = pool.alloc(3)
    pool.free(pages[:1])
    before = list(pool._free)
    for bad in ([pages[0]],            # double free
                [pages[1], pages[1]],  # the same page twice in one call
                [8], [-1],             # out of range
                [pages[2], 99]):       # a good page before a bad one
        with pytest.raises(ValueError):
            pool.free(bad)
        assert pool._free == before
    pool.free(pages[1:])
    assert pool.used == 0


def _requests(cfg, n):
    rng = np.random.default_rng(7)
    return [Request(uid=u + 1,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 30))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(4, 24)))
            for u in range(n)]


def test_engine_pages_and_tokens_equal_the_list_scan(cfg, monkeypatch):
    """A serving run with many retirements hands out the same pages and
    emits bitwise the same tokens as with the list-scan ``free``."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests(cfg, 12)
    bs = BatchedServeConfig(slots=3, max_seq=64)

    def run():
        allocs = []
        alloc = PagedKVPool.alloc

        def logged(self, n):
            allocs.append(alloc(self, n))
            return allocs[-1]

        with monkeypatch.context() as mp:
            mp.setattr(PagedKVPool, "alloc", logged)
            out = BatchedEngine(cfg, bs, params).run(reqs)
        return out, allocs

    new, new_allocs = run()

    def list_scan_free(self, pages):
        for p in pages:
            if not 0 <= p < self.n_pages or p in self._free:
                raise ValueError(f"bad free of page {p}")
        self._free.extend(sorted(pages, reverse=True))
        self._free_set.update(pages)

    monkeypatch.setattr(PagedKVPool, "free", list_scan_free)
    old, old_allocs = run()
    assert new_allocs == old_allocs and len(new_allocs) > len(reqs)
    for r in reqs:
        np.testing.assert_array_equal(new[r.uid], old[r.uid])
