"""The tiny CPU cell and the float32 reference at GQA 4:1: 8 query heads over
2 kv heads (the grouped paged decode, R = 4 query rows per kv head), heads
wider than hidden_size / heads, and Mistral's rope_theta. Each test runs the
same checks as its GQA 2:1 twin, on this geometry."""
import json

import pytest

import test_bench_control
import test_bench_harness
import test_bench_reference
import test_bench_span_live

GQA4 = {"num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
        "rope_theta": 1e6}


@pytest.fixture
def gqa4_root(tiny_root):
    """``tiny_root`` with its ``tiny`` configuration made GQA 4:1. The cell
    and its ``mean_gap`` limit stay: at this size (CPU, seeds 5-16, 119-161
    served tokens each) the program reads 0.00014-0.00073 and its control
    0.0024-0.0093."""
    path = tiny_root / "bench" / "configs" / "tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **GQA4)))
    return tiny_root


def test_gqa4_cell_from_added_files_runs_and_is_correct(gqa4_root, run_tiny):
    test_bench_harness.test_new_cell_from_added_files_runs_and_is_correct(
        gqa4_root, run_tiny)


def test_gqa4_control_is_not_correct(gqa4_root, run_tiny):
    test_bench_control.test_control_is_not_correct(gqa4_root, run_tiny)


def test_gqa4_traced_run_reads_span_live_share(gqa4_root, run_tiny,
                                               monkeypatch):
    test_bench_span_live.test_reads_a_traced_tiny_run(gqa4_root, run_tiny,
                                                      monkeypatch)


def test_gqa4_reference_matches_program_model_in_float32():
    test_bench_reference.test_reference_matches_program_model_in_float32(
        dict(GQA4))
