"""Chip benchmark of the F2P serving stack.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell (``bench/workloads/<cell>.json``) on the chips of this
machine and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``checks``: each number that
decides ``correct`` beside its limit. The same comparisons close standard
error. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled stretch of the window.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout that holds the program (``src/repro``), it exits non-zero and
prints no result. The persistent compilation cache lives in ``.jax_cache/``
of the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another place.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # small programs too: a run should compile nothing the last one did
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: src/repro not found; run from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness

    cell = harness.load_cell(args.workload)
    bench = harness.benchmark()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        device = harness.device_info(cell.spec["chips"])
    except SystemExit as e:
        log(str(e))
        return 3
    log(f"bench: {args.workload} seed {args.seed} on {device['count']} x "
        f"{device['kind']}; compile cache {enable_compile_cache()}")
    res = cell.runner.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START, log=log)
    if args.trace:
        metrics = harness.read_layer_metrics(args.workload, bench,
                                             res.trace_ctx)
        device = dict(device, busy_s=res.trace_ctx.device.busy_s,
                      window_s=res.trace_ctx.device.window_s)
    else:
        metrics = {m["name"]: {"value": float(res.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in harness.metrics_for(args.workload, bench,
                                                "end_to_end")}
    device["memory_peak_bytes"] = res.memory_peak_bytes
    for name, c in res.checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(harness.result_line(
        correct=res.correct, attempted=res.attempted, failed=res.failed,
        metrics=metrics, device=device, checks=res.checks,
        breakdown=res.breakdown if args.trace else None), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
