"""Device time by program and idle gaps by engine phase (bench/programs.py),
the readers built on them, and a pin of the readers that came before."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, programs, work
from bench import trace_reduce as TR

DATA = Path(__file__).with_name("data")
METRICS = harness.ROOT / "bench" / "metrics"
NEW = ("round_roofline.serve", "prefill_mfu.serve", "kv_write_roofline.serve",
       "scheduler_share.serve")


def _op(name, start, dur, program, layer):
    return programs.ProgramOp(name, start, dur, program, layer)


def _synthetic():
    # window 0-100 ns on one device: a prefill program 0-30 with two ops, a
    # kv store 30-40, a round 50-90 whose loop holds two ops; the same
    # instruction (fusion.1) runs in the prefill and the round. Phases:
    # schedule 0-45 holding prefill 0-40 (launch 0-5, wait 5-30, store
    # 30-40); round 45-95 (prep 45-50, launch 50-52, wait 52-95); harvest
    # 95-100.
    ops = [_op("fusion.1", 2, 10, "jit_prefill_step", "prefill"),
           _op("_quantize_packed_pallas_jit.8", 12, 10, "jit_prefill_step",
               "prefill"),
           _op("copy.1", 31, 8, "jit__store_row_all", "kv_store"),
           _op("while.3", 50, 40, "jit_round_fn", "round"),
           _op("fusion.1", 50, 20, "jit_round_fn", "round"),
           _op("_quantize_packed_pallas_jit.9", 75, 10, "jit_round_fn",
               "round")]
    mods = [(0, 30, "jit_prefill_step", "prefill"),
            (30, 40, "jit__store_row_all", "kv_store"),
            (50, 90, "jit_round_fn", "round")]
    phases = [("schedule", 0, 45), ("prefill", 0, 40),
              ("prefill.launch", 0, 5), ("prefill.wait", 5, 30),
              ("prefill.store", 30, 40), ("round", 45, 95),
              ("round.prep", 45, 50), ("round.launch", 50, 52),
              ("round.wait", 52, 95), ("harvest", 95, 100)]
    return programs.ProgramTrace(window=(0, 100), devices={"A": ops},
                                 host_spans=phases, modules={"A": mods})


def test_program_seconds_and_attribution():
    t = _synthetic()
    assert t.program_seconds("prefill") == pytest.approx(30e-9)
    assert t.program_seconds("kv_store") == pytest.approx(10e-9)
    assert t.program_seconds("round") == pytest.approx(40e-9)
    assert t.program_seconds("upload") == 0
    assert t.attributed_share() == 1.0
    # busy and per-name seconds are those of the plain reduction
    assert t.busy_s == pytest.approx(58e-9)
    assert t.op_seconds(lambda n: "quantize_packed" in n) == \
        pytest.approx(20e-9)


def test_same_instruction_in_two_programs_is_two_entries():
    top = dict(_synthetic().top_ops(10))
    assert top["round/fusion.1"] == pytest.approx(20e-9)
    assert top["prefill/fusion.1"] == pytest.approx(10e-9)
    assert not any(n.split("/", 1)[1].startswith("while") for n in top)


def test_gaps_named_by_innermost_phase():
    # idle: 0-2 (midpoint 1: prefill.launch), 22-31 (26.5: prefill.wait),
    # 39-50 (44.5: schedule, after the prefill), 70-75 and 85-100 (72.5,
    # 92.5: round.wait; the loop's own span is no work)
    assert _synthetic().idle_gaps(10) == [
        ["round.wait", pytest.approx(15e-9)],
        ["schedule", pytest.approx(11e-9)],
        ["prefill.wait", pytest.approx(9e-9)],
        ["round.wait", pytest.approx(5e-9)],
        ["prefill.launch", pytest.approx(2e-9)]]
    assert _synthetic().span_at(120) == programs.OUTSIDE


def test_op_time_outside_the_table_voids_the_trace():
    t = _synthetic()
    assert programs.attributed(SimpleNamespace(programs=t)) is t
    # work moved into a program the table does not list: 5 of 63 ns
    t.devices["A"].append(_op("fusion.7", 92, 5, "jit_moved_out",
                              programs.OTHER))
    assert t.attributed_share() == pytest.approx(58 / 63)
    assert programs.attributed(SimpleNamespace(programs=t)) is None


def test_phase_seconds_cut_nested_phases():
    t = _synthetic()
    assert t.phase_seconds(("schedule",)) == pytest.approx(45e-9)
    assert t.phase_seconds(("schedule", "round.prep", "harvest"),
                           minus=("prefill",)) == pytest.approx(15e-9)


def test_module_names_drop_the_fingerprint():
    assert programs.module_name("jit_round_fn(3854724545798087085)") == \
        "jit_round_fn"
    assert programs.module_name("jit__io_delta") == "jit__io_delta"


# -- the recorded chip traces ----------------------------------------------
def _unzip(tmp_path, name):
    path = tmp_path / name.replace(".gz", "")
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def phases_trace(tmp_path_factory):
    """A job of the tiny cell (2 layers) traced on a TPU v5 lite with the
    engine's phase spans, and what the runner's readers saw of it."""
    from repro.serve.batched import PROGRAMS

    tmp = tmp_path_factory.mktemp("phases")
    pt = programs.reduce(_unzip(tmp, "tiny_tpu_phases.xplane.pb.gz"),
                         programs=PROGRAMS)
    ctx = json.loads((DATA / "tiny_tpu_phases.ctx.json").read_text())
    return pt, ctx


def test_every_op_belongs_to_a_program(phases_trace):
    pt, _ = phases_trace
    ops = [op for d in pt.devices.values() for op in d]
    assert ops and all(op.program for op in ops)
    assert pt.attributed_share() >= 0.99
    assert {"round", "prefill", "kv_store", "upload"} <= {
        op.layer for op in ops}


def test_recorded_instruction_names_split_by_program(phases_trace):
    pt, _ = phases_trace
    layers: dict[str, set] = {}
    for ops in pt.devices.values():
        for op in ops:
            if not op.name.startswith(TR.CONTAINERS):
                layers.setdefault(op.name, set()).add(op.layer)
    shared = {n for n, ls in layers.items() if len(ls) > 1}
    assert shared
    names = [n for n, _ in pt.top_ops(10 ** 6)]
    for n in shared:
        assert sum(x.split("/", 1)[1] == n for x in names) == len(layers[n])


def test_no_gap_inside_the_job_falls_outside_the_phases(phases_trace):
    pt, _ = phases_trace
    gaps = pt.idle_gaps(10 ** 6)
    assert gaps and all(s > 0 for _, s in gaps)
    outside = sum(s for n, s in gaps if n == programs.OUTSIDE)
    # what lies outside every phase: the job's first and last
    # microseconds and the loop's turn between phases
    assert outside <= 0.01 * pt.window_s
    assert all(s < 1e-3 for n, s in gaps if n == programs.OUTSIDE)


def _ctx(pt, ctx):
    return SimpleNamespace(device=pt, programs=pt,
                           **{k: v for k, v in ctx.items() if k != "window"})


def test_new_readers_on_the_recorded_job(phases_trace):
    pt, ctx = phases_trace
    c = _ctx(pt, ctx)
    got = {n: harness.load_module(METRICS / f"{n}.py").read(c) for n in NEW}
    for name in NEW:
        assert 0 < got[name] <= 100, (name, got[name])


def test_an_unlisted_program_voids_the_program_metrics(tmp_path,
                                                      phases_trace):
    """The recorded job read with a table that misses the prefill program:
    its time reads ``other`` and would drop out of prefill_mfu's
    denominator, so every reader of program seconds reads None; the phase
    reader reads as before."""
    from repro.serve.batched import PROGRAMS

    pt, ctx = phases_trace
    table = {k: v for k, v in PROGRAMS.items() if v != "prefill"}
    short = programs.reduce(_unzip(tmp_path, "tiny_tpu_phases.xplane.pb.gz"),
                            programs=table)
    assert short.attributed_share() < programs.MIN_ATTRIBUTED
    read = lambda name, t: harness.load_module(
        METRICS / f"{name}.py").read(_ctx(t, ctx))
    for name in ("round_roofline.serve", "prefill_mfu.serve",
                 "kv_write_roofline.serve"):
        assert read(name, pt) is not None
        assert read(name, short) is None, name
    assert read("scheduler_share.serve", short) == \
        read("scheduler_share.serve", pt)


def test_new_readers_report_nothing_without_the_programs_table(
        phases_trace, monkeypatch):
    """A program that has no PROGRAMS table, no phases and no kept-work
    counters (the commit before them): every new reader reads None."""
    from repro.serve import batched

    pt, ctx = phases_trace
    monkeypatch.delattr(batched, "PROGRAMS")
    c = SimpleNamespace(device=pt, **{k: v for k, v in ctx.items()
                                      if k != "window"})
    c.rounds = [{"active": r["active"], "pool_used": r["pool_used"]}
                for r in ctx["rounds"]]
    for name in NEW:
        assert harness.load_module(METRICS / f"{name}.py").read(c) is None
    # with the table but no phases and no kept-work counters, the readers
    # that need those read None
    bare = programs.ProgramTrace(window=pt.window, devices=pt.devices,
                                 host_spans=[], modules=pt.modules)
    c2 = SimpleNamespace(**vars(c), programs=bare)
    for name in ("round_roofline.serve", "kv_write_roofline.serve",
                 "scheduler_share.serve"):
        assert harness.load_module(METRICS / f"{name}.py").read(c2) is None


# -- the readers that came before: values pinned on the old fixture --------
PINNED = {"device_idle.serve": 95.5659168231846,
          "host_share.serve": 3.989907491545086,
          "prefill_share.serve": 36.175007865122595,
          "attn_paged_roofline.serve": 0.7901305879326139,
          "mfu.serve": 0.002326824525153621}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_earlier_readers_unchanged_on_the_old_fixture(tmp_path, name):
    dev = TR.reduce(_unzip(tmp_path, "tiny_tpu.xplane.pb.gz"),
                    window="bench_job",
                    spans=("round", "prefill", "prefill_group"))
    conftest = harness.load_module(Path(__file__).with_name("conftest.py"))
    w0 = dev.window[0]
    ctx = SimpleNamespace(
        device=dev, host_window_s=dev.window_s,
        spans=[(n, (s - w0) / 1e9, (e - w0) / 1e9)
               for n, s, e in dev.host_spans],
        rounds=[{"active": 4.0, "pool_used": 17.0 + 4 * i}
                for i in range(7)],
        prompt_lens=[20] * 8, output_lens=[24] * 8,
        model=harness.model_dims(conftest.TINY_CONFIG), sync_every=8,
        page_tokens=8, kv_bits=8, peaks=work.peaks("TPU v5 lite"))
    assert harness.load_module(METRICS / f"{name}.py").read(ctx) == \
        PINNED[name]
