"""chip_smoke.py's phases, run on the CPU at smoke size.

The script itself refuses to run without a TPU; these tests drive its
building blocks with the smoke configs so a broken phase shows up here
before it costs chip time.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from repro.configs import smoke_config  # noqa: E402
from repro.models import init_params  # noqa: E402


@pytest.fixture(scope="module")
def llama():
    import dataclasses

    cfg = dataclasses.replace(smoke_config("llama3_2_3b"),
                              fused_attention=True)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_script_refuses_cpu():
    """No TPU: non-zero exit and no result line."""
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_serve_requests_complete_and_round_compiles(llama):
    cfg, params = llama
    reqs = chip_smoke.make_requests(cfg.vocab_size)
    eng, out = chip_smoke.serve_once(cfg, params, reqs, "cpu")
    chip_smoke.check_outputs(reqs, out, cfg.vocab_size)
    hlo, mem = chip_smoke.decode_round_hlo(eng)
    assert "while" in hlo and mem is not None
    with pytest.raises(AssertionError):
        chip_smoke.check_outputs(reqs, {**out, 1: out[1][:-1]},
                                 cfg.vocab_size)


def test_decode_logits_backends_agree_on_cpu(llama):
    """pallas_interpret and xla run the same per-tile math: on the CPU the
    paged decode step agrees bit for bit."""
    cfg, params = llama
    reqs = chip_smoke.make_requests(cfg.vocab_size)[:3]
    a = chip_smoke.decode_logits(cfg, params, reqs, "pallas_interpret")
    b = chip_smoke.decode_logits(cfg, params, reqs, "xla")
    assert a.shape == (3, cfg.vocab_size)
    np.testing.assert_array_equal(a, b)


def test_train_losses_match_across_mesh_sizes(monkeypatch):
    monkeypatch.setattr(chip_smoke, "TRAIN_SEQ", 32)
    monkeypatch.setattr(chip_smoke, "TRAIN_BATCH", 4)
    monkeypatch.setattr(chip_smoke, "TRAIN_STEPS", 2)
    cfg = smoke_config("xlstm_125m")
    devs = jax.devices()[:2]
    many = chip_smoke.train_losses(cfg, devs, (len(devs), 1))
    one = chip_smoke.train_losses(cfg, devs[:1], (1, 1))
    assert len(many) == 2 and all(np.isfinite(many))
    np.testing.assert_allclose(many, one, rtol=chip_smoke.LOSS_RTOL)
