"""Record the tiny test cell's traced job as a test fixture.

    PYTHONPATH=src python tests/bench/record_tiny_trace.py OUT_DIR [--seed N]

Runs the CPU test cell of ``conftest.py`` (a 2-layer engine) through the
``serve_offline`` runner with ``trace`` on, then writes to ``OUT_DIR`` the
job's profile (``tiny_tpu_phases.xplane.pb.gz``) and what the runner's
readers saw of it besides (``tiny_tpu_phases.ctx.json``: the engine's
``slots`` counters, prompt and output lengths, settings). Run it on a TPU
to record a device trace; on the CPU the profile has no device plane.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
NAME = "tiny_tpu_phases"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out", type=Path)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from bench import harness, trace_reduce

    conftest = harness.load_module(Path(__file__).with_name("conftest.py"))
    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        shutil.copytree(REPO / "bench", root / "bench")
        b = root / "bench"
        (b / "configs" / "tiny.json").write_text(
            json.dumps(conftest.TINY_CONFIG))
        (b / "traffic" / "tiny-mix.json").write_text(
            json.dumps(conftest.TINY_MIX))
        (b / "workloads" / "tiny.cell.json").write_text(
            json.dumps(conftest.TINY_CELL))
        cell = harness.load_cell("tiny.cell", root=root)
        res = cell.runner.run(cell, seed=args.seed, seconds=0.3, trace=True,
                              t_start=time.perf_counter(), log=print)
    ctx = res.trace_ctx
    args.out.mkdir(parents=True, exist_ok=True)
    xplane = Path(trace_reduce.find_xplane(
        str(harness.ROOT / ".bench_out" / "trace")))
    (args.out / f"{NAME}.xplane.pb.gz").write_bytes(
        gzip.compress(xplane.read_bytes(), 9))
    keep = ("host_window_s", "spans", "rounds", "prompt_lens", "output_lens",
            "model", "sync_every", "page_tokens", "kv_bits", "peaks")
    ctx_json = {k: getattr(ctx, k) for k in keep}
    ctx_json["window"] = list(ctx.device.window)
    (args.out / f"{NAME}.ctx.json").write_text(json.dumps(ctx_json))
    print(json.dumps({"correct": res.correct, "device": ctx.device.devices
                      and sorted(ctx.device.devices), "busy_s":
                      ctx.device.busy_s, "window_s": ctx.device.window_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
