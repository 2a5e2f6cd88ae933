"""A tiny serving cell, defined only by files added to a copy of ``bench/``,
that runs on the CPU."""
import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "source": "a small dense decoder for CPU tests", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 1024,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "reduced": []}
TINY_MIX = {"generator": "offline_jobs",
            "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
            "output": {"median": 24, "sigma": 0.3, "min": 16, "max": 32}}
# mean_gap at this size (CPU, seeds 5-12, about 190 served tokens each):
# the bfloat16 program with 8-bit F2P KV reads 0.00007-0.00043, its control
# (fp8 weights and activations, 4-bit KV) 0.00308-0.00717
TINY_CELL = {"config": "tiny", "traffic": "tiny-mix",
             "runner": "serve_offline", "chips": 1, "why": "CPU test cell",
             "job_requests": 8,
             "serve": {"slots": 4, "max_seq": 128, "prefill_group": 2,
                       "scheduler": "fifo", "preempt_patience": 10 ** 9},
             "check": {"sequences": 6, "mean_gap": 0.0012}}


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    """A checkout-like root whose ``bench/`` is the repo's plus the new
    files of one cell: ``tiny.cell``."""
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(TINY_MIX))
    (b / "workloads" / "tiny.cell.json").write_text(json.dumps(TINY_CELL))
    return tmp_path


def _run_tiny(root: Path, *, seed: int = 5, trace: bool = False,
              seconds: float = 0.3):
    import time

    from bench import harness

    lines: list[str] = []
    cell = harness.load_cell("tiny.cell", root=root)
    res = cell.runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                          t_start=time.perf_counter(), log=lines.append)
    return res, lines


@pytest.fixture
def run_tiny():
    """Drive the tiny cell through its runner, the chip check skipped:
    ``run_tiny(root, seed=..., trace=...) -> (result, log lines)``."""
    return _run_tiny
