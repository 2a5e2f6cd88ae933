"""The benchmark's traffic generator: seeded, and the same work per seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def _traffic(mix: str, seed: int, n: int = 48, vocab: int = 32064):
    spec = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    gen = harness.load_module(ROOT / "bench" / "traffic"
                              / f"{spec['generator']}.py")
    return spec, gen.make(spec, job_requests=n, vocab=vocab, seed=seed)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    _, a = _traffic(mix, 2 ** 33 + 5)
    _, b = _traffic(mix, 2 ** 33 + 5)
    for j in range(2):
        for x, y in zip(a.job(j), b.job(j)):
            assert x.uid == y.uid and x.max_new == y.max_new
            np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_requests_same_sizes(mix):
    _, a = _traffic(mix, 1)
    _, b = _traffic(mix, 2)
    ja, jb = a.job(0), b.job(0)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(ja, jb))
    # every seed and every job holds the same (prompt, output) sizes in the
    # same order: the order sets how a job drains, so it is part of the work
    sizes = [(len(r.prompt), r.max_new) for r in ja]
    for job in (jb, a.job(3)):
        assert [(len(r.prompt), r.max_new) for r in job] == sizes
    assert len(set(sizes)) > 1


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_clipped_ids_in_vocab_uids_unique(mix):
    spec, t = _traffic(mix, 9, vocab=1000)
    uids = set()
    for j in range(3):
        for r in t.job(j):
            assert spec["prompt"]["min"] <= len(r.prompt) <= spec["prompt"]["max"]
            assert spec["output"]["min"] <= r.max_new <= spec["output"]["max"]
            assert r.prompt.dtype == np.int32
            assert 0 <= r.prompt.min() and r.prompt.max() < 1000
            uids.add(r.uid)
    assert len(uids) == 3 * t.n


def test_quantile_lengths_follow_the_median():
    gen = harness.load_module(ROOT / "bench" / "traffic" / "offline_jobs.py")
    v = gen.lengths({"median": 768, "sigma": 0.5, "min": 256, "max": 1536},
                    101)
    assert v[50] == 768 and list(v) == sorted(v)
    assert v[0] >= 256 and v[-1] <= 1536
