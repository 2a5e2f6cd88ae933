"""The harness: cells found by file name, the contract of BENCHMARK.json,
and no result without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_cell_from_added_files_runs_and_is_correct(tiny_root, run_tiny):
    res, log = run_tiny(tiny_root)
    assert res.correct, res.checks
    assert res.attempted >= 8 and res.failed == 0
    assert set(res.e2e) == {"out_tok_s", "tpot_ms", "setup_s"}
    assert all(v > 0 for v in res.e2e.values())
    assert any(" 0 compile events inside" in line for line in log), log


def test_traced_run_reads_host_metrics(tiny_root, run_tiny, monkeypatch):
    from bench import work

    monkeypatch.setattr(work, "peaks", lambda kind: {
        "bf16_flops_s": 1e12, "hbm_bytes_s": 1e11})
    res, _ = run_tiny(tiny_root, trace=True)
    ctx = res.trace_ctx
    assert ctx.rounds and ctx.spans and ctx.host_window_s > 0
    bench = dict(BENCH, per_layer=[dict(m, workloads=["tiny.cell"])
                                   for m in BENCH["per_layer"]])
    got = harness.read_layer_metrics("tiny.cell", bench, ctx,
                                     root=tiny_root)
    for name in ("host_share.serve", "prefill_share.serve", "mfu.serve"):
        assert 0 < got[name]["value"] < 100
    # the CPU writes no device plane: device readers find nothing to read
    assert "device_idle.serve" not in got
    assert "attn_paged_roofline.serve" not in got


def _run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run_bench(REPO, "--workload", BENCH["workloads"][0]["name"],
                   "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(REPO / path, tmp_path / path)
    p = _run_bench(tmp_path, "--workload", BENCH["workloads"][0]["name"],
                   "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries_match_their_files(cfg):
    path = REPO / cfg["file"]
    assert path.is_file() and cfg["file"].startswith("bench/configs/")
    data = json.loads(path.read_text())
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert key in data and data["published"][key] != data[key]
    assert 1 <= len(cfg["why"]) <= 200


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entries_match_their_files(wl):
    cell = harness.load_cell(wl["name"])
    assert cell.spec["config"] == wl["config"]
    assert cell.spec["traffic"] == wl["traffic"]
    assert cell.spec["chips"] == wl["chips"] in (1, 4)
    assert cell.spec["why"] == wl["why"] and len(wl["why"]) <= 200
    e2e = {m["name"] for m in harness.metrics_for(wl["name"], BENCH,
                                                  "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(wl["name"], BENCH, "per_layer")


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_readers_declare_their_layer(m):
    reader = harness.load_module(REPO / "bench" / "metrics"
                                 / f"{m['name']}.py")
    assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m["workloads"]) <= cells
    for cell in m["workloads"]:
        assert m["moves"] in {e["name"] for e in harness.metrics_for(
            cell, BENCH, "end_to_end")}


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_unknown_cell_is_an_error():
    with pytest.raises(FileNotFoundError):
        harness.load_cell("no-such-cell")


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_warm_plan_covers_every_length_group_and_span(wl):
    from types import SimpleNamespace

    from bench.runners.serve_offline import warm_plan
    from repro.serve import BatchedServeConfig

    cell = harness.load_cell(wl["name"])
    s = {k: tuple(v) if isinstance(v, list) else v
         for k, v in cell.spec["serve"].items()}
    bs = BatchedServeConfig(**s)
    T = 8
    buckets = bs.prefill_buckets or tuple(
        b for b in (2 * T, 4 * T, 8 * T, 16 * T) if b <= bs.max_seq)
    eng = SimpleNamespace(page_tokens=T, buckets=buckets)
    t = cell.generator.make(cell.traffic, job_requests=cell.spec[
        "job_requests"], vocab=100, seed=1)
    plan = warm_plan(eng, bs, t.prompt_lens, t.output_lens)
    assert all(len(b) <= bs.slots for b in plan)
    prefill_only = [b for b in plan if all(m == 1 for _, m in b)]
    for L in set(int(x) for x in t.prompt_lens):
        sizes = set()
        for b in prefill_only:
            bk = next(x for x in buckets if L <= x)
            same = [x for x, _ in b if next(y for y in buckets if x <= y) == bk]
            if L in same:
                sizes.add(len(same))
        assert {1 if n == 1 else 2 if n == 2 else 4 for n in sizes} >= {
            g for g in (1, 2, 4) if g <= bs.prefill_group}
    decode = [b[0] for b in plan if b[0][1] > 1]
    assert decode and all(L + m <= bs.max_seq for L, m in decode)
