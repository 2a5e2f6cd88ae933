"""Launch-layer tests: sharding rules, spec sanitation, and an end-to-end
mini dry-run (lower+compile a smoke config on a real 2x2 host-device mesh)."""
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, full_config, input_specs, smoke_config
from repro.launch.roofline import Roofline, active_params, model_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sanitize_spec_drops_nondivisible():
    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import sanitize_spec

    mesh = make_mesh((1,), ("model",))

    # fake a 16-wide axis via a mesh dict stub
    class M:
        shape = {"model": 16, "data": 4}

    s = sanitize_spec((24, 64), P("model", "data"), M)
    assert s == P(None, "data")
    s2 = sanitize_spec((32, 3), P("model", "data"), M)
    assert s2 == P("model", None)


def test_active_params_moe():
    cfg = full_config("llama4_scout_17b")
    n_act = active_params(cfg)
    n_tot = cfg.param_count()
    assert n_act < n_tot / 4          # 16 experts, top-1
    assert 10e9 < n_act < 30e9        # "17B active"


def test_model_flops_kinds():
    cfg = full_config("llama3_2_3b")
    t = model_flops(cfg, "train_4k", 4096, 256, "train")
    p = model_flops(cfg, "prefill_32k", 32768, 32, "prefill")
    d = model_flops(cfg, "decode_32k", 32768, 128, "decode")
    assert t == pytest.approx(6 * active_params(cfg) * 4096 * 256)
    assert p == pytest.approx(2 * active_params(cfg) * 32768 * 32)
    assert d == pytest.approx(2 * active_params(cfg) * 128)


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch.roofline import peaks

    assert peaks("TPU v5 lite")["flops"] == 197e12
    r = Roofline(arch="a", shape="s", mesh="m", n_devices=1,
                 device_kind="cpu", hlo_flops=1.0, hlo_bytes=1.0,
                 collective_bytes=0.0, collective_bytes_naive=0,
                 model_flops=1.0, memory_per_device={}, per_op={})
    with pytest.raises(ValueError, match="no peak table"):
        r.t_compute


def test_roofline_properties():
    r = Roofline(arch="a", shape="s", mesh="m", n_devices=256,
                 device_kind="TPU v5 lite", hlo_flops=197e12, hlo_bytes=819e9 * 2,
                 collective_bytes=50e9 * 3, collective_bytes_naive=0,
                 model_flops=197e12 * 256 * 0.5, memory_per_device={},
                 per_op={})
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(3.0)
    assert r.bottleneck == "collective"
    assert r.roofline_fraction == pytest.approx(0.5 / 3.0)


def test_input_specs_cover_all_cells():
    for arch in ("llama3_2_3b", "whisper_large_v3", "internvl2_1b",
                 "jamba_1_5_large"):
        cfg = full_config(arch)
        for shape in SHAPES:
            specs = input_specs(cfg, shape)
            assert all(hasattr(v, "shape") for v in specs.values())
            if cfg.frontend == "vision":
                assert "patches" in specs
            if cfg.is_encdec:
                assert "frames" in specs


def test_mini_dryrun_2x2_mesh():
    """Full launch machinery on a REAL (2,2)=data,model host-device mesh with
    a smoke config: lower + compile + roofline terms."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.launch import roofline as RL
from repro.launch.dryrun import lower_cell
from repro.launch.shardings import rules_for
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
import repro.configs.registry as REG
# mutate in place: the dict object is shared across module bindings
REG.SHAPES["train_4k"] = (64, 4, "train")
REG.SHAPES["decode_32k"] = (64, 4, "decode")
for shape in ("train_4k", "decode_32k"):
    compiled, cfg, meta = lower_cell("llama4_scout_17b", shape, mesh,
                                     cfg=smoke_config("llama4_scout_17b"))
    rl = RL.analyze(compiled, arch="scout-smoke", shape=shape,
                    mesh_name="2x2", n_devices=4, device_kind="TPU v5 lite",
                    cfg=cfg, seq=64, gbatch=4,
                    kind=REG.SHAPES[shape][2])
    assert rl.hlo_flops > 0, shape
    assert rl.t_memory > 0, shape
    print("MINI_OK", shape, rl.bottleneck)
"""
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("MINI_OK") == 2
