"""``span_live_share.serve``: the live share of the KV positions the decode
rounds' attention covered, read from the engine's ``slots`` counters."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness

READER = harness.ROOT / "bench" / "metrics" / "span_live_share.serve.py"
DATA = Path(__file__).with_name("data")


def _read(ctx):
    return harness.load_module(READER).read(ctx)


def test_reads_a_traced_tiny_run(tiny_root, run_tiny, monkeypatch):
    from bench import work

    # the runner asks for the chip's peaks, which this reader never reads
    monkeypatch.setattr(work, "peaks", lambda kind: {})
    res, _ = run_tiny(tiny_root, trace=True)
    rounds = res.trace_ctx.rounds
    assert rounds and all(r["kv_attended"] >= r["kv_live"] for r in rounds)
    assert 0 < _read(res.trace_ctx) <= 100


def test_nothing_to_read_on_the_recorded_job():
    """The recorded chip job predates ``kv_attended``: no value, no error."""
    ctx = json.loads((DATA / "tiny_tpu_phases.ctx.json").read_text())
    assert ctx["rounds"] and "kv_attended" not in ctx["rounds"][0]
    assert _read(SimpleNamespace(rounds=ctx["rounds"])) is None


def test_share_of_the_summed_counters():
    rounds = [{"kv_live": 300, "kv_attended": 1024},
              {"kv_live": 212, "kv_attended": 1024},
              {"kv_live": 40}]                   # an older event: not read
    assert _read(SimpleNamespace(rounds=rounds)) == pytest.approx(25.0)
