"""Trace reduction: busy union, idle gaps by host span, ops by name."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).with_name("data")


def _trace():
    # window 0-100 ns; device A busy 10-30 (two overlapping ops) and 60-70;
    # device B busy 0-100; device C runs one loop over 0-100 whose body ops
    # take 0-40 and 70-100; host spans: round 0-50, prefill 55-80
    a = [TR.Op("fusion.1", 10, 15), TR.Op("_attention_paged_pallas.15", 20, 10),
         TR.Op("fusion.2", 60, 10), TR.Op("late", 95, 20)]
    b = [TR.Op("fusion.1", 0, 100), TR.Op("while.3", 0, 100)]
    c = [TR.Op("while.7", 0, 100), TR.Op("fusion.3", 0, 40),
         TR.Op("fusion.4", 70, 30)]
    return TR.DeviceTrace(window=(0, 100), devices={"A": a, "B": b, "C": c},
                          host_spans=[("round", 0, 50),
                                      ("prefill", 55, 80)])


def test_busy_is_the_union_averaged_over_devices():
    t = _trace()
    # A: 10-30, 60-70, 95-100 (clipped) = 35 ns; B: 100 ns; C: the ops in
    # its loop, 0-40 and 70-100 = 70 ns (the loop's own span is no work)
    assert t.busy_s == pytest.approx((35 + 100 + 70) / 3 / 1e9)
    assert t.window_s == pytest.approx(100 / 1e9)


def test_gap_inside_a_loop_is_idle():
    t = TR.DeviceTrace(window=(0, 100), host_spans=[("round", 0, 100)],
                       devices={"C": [TR.Op("while.7", 0, 100),
                                      TR.Op("fusion.3", 0, 40),
                                      TR.Op("fusion.4", 70, 30)]})
    assert t.busy_s == pytest.approx(70 / 1e9)
    assert t.idle_gaps(10) == [["round", pytest.approx(30e-9)]]


def test_ops_by_name():
    t = _trace()
    assert t.op_seconds(lambda n: "paged" in n) == pytest.approx(10e-9 / 3)
    assert "while.3" not in {n for n, _ in t.top_ops(10)}
    top = t.top_ops(2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(115e-9 / 3)


def test_idle_gaps_named_by_host_span():
    gaps = _trace().idle_gaps(10)
    # A idles 0-10 (round), 30-60 (midpoint 45: round), 70-95 (82.5:
    # outside any span); B never idles; C idles 40-70 (55: prefill), in
    # its loop
    assert gaps == [["round", pytest.approx(30e-9)],
                    ["prefill", pytest.approx(30e-9)],
                    [TR.OUTSIDE, pytest.approx(25e-9)],
                    ["round", pytest.approx(10e-9)]]


def test_recorded_chip_trace(tmp_path):
    """One job of a tiny cell traced on a TPU v5 lite (gzipped xplane)."""
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "tiny_tpu.xplane.pb.gz")
                                     .read_bytes()))
    t = TR.reduce(str(path), window="bench_job",
                  spans=("round", "prefill", "prefill_group"))
    assert t.devices and 0 < t.busy_s < t.window_s
    assert t.host_spans and {n for n, _, _ in t.host_spans} <= {
        "round", "prefill", "prefill_group"}
    assert t.top_ops(5) and t.idle_gaps(5)
    # while loops are containers: never listed among the ops
    assert not any(n.startswith("while") for n, _ in t.top_ops(10))
    assert t.op_seconds(lambda n: "attention_paged" in n) > 0


def test_op_names_keep_instruction_shape_and_kind():
    text = ("%fusion.145 = f32[2048,8,32]{0,2,1:T(8,128)S(1)} fusion(f32[6,8]"
            "{1,0} %a), kind=kCustom")
    assert TR.op_name(text) == "fusion.145 f32[2048,8,32] fusion"
    assert TR.op_name("copy.3") == "copy.3"
