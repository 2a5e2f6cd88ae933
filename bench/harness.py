"""Finds a cell's pieces by name and assembles the result line.

A cell is ``bench/workloads/<cell>.json``: its configuration, its traffic
mix, the runner that drives it and the runner's settings. A configuration
is ``bench/configs/<config>.json``, a traffic mix ``bench/traffic/<mix>.json``
read by the generator it names (``bench/traffic/<generator>.py``), a runner
``bench/runners/<runner>.py`` and a per-layer metric
``bench/metrics/<metric>.py``. ``BENCHMARK.json`` says which metrics each
cell reports. Nothing here branches on the name of a cell, a configuration,
a mix or a metric, so a later cell, mix or metric is a set of new files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict          # the workload file
    config: dict        # the configuration file
    traffic: dict       # the traffic mix file
    generator: ModuleType
    runner: ModuleType


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    name = "bench._loaded." + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> Cell:
    b = root / "bench"
    path = b / "workloads" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no cell {name!r}: {path} is missing")
    spec = _json(path)
    traffic = _json(b / "traffic" / f"{spec['traffic']}.json")
    return Cell(name=name, spec=spec,
                config=_json(b / "configs" / f"{spec['config']}.json"),
                traffic=traffic,
                generator=load_module(b / "traffic"
                                      / f"{traffic['generator']}.py"),
                runner=load_module(b / "runners" / f"{spec['runner']}.py"))


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def metrics_for(cell: str, bench: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports.
    A per-layer metric without a ``workloads`` list belongs to every cell
    that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_layer_metrics(cell: str, bench: dict, ctx, root: Path = ROOT
                       ) -> dict:
    """Run each per-layer reader of ``cell`` over the traced run's context;
    a reader that finds nothing returns None and its metric is left out."""
    out = {}
    for m in metrics_for(cell, bench, "per_layer"):
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def model_dims(config: dict) -> dict:
    """The model's sizes as the reference and the weights read them."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rope_theta", "rms_norm_eps", "tie_word_embeddings",
            "torch_dtype")
    m = {k: config[k] for k in keys}
    m["head_dim"] = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    return m


def device_info(n_chips: int) -> dict:
    """Platform, kind and count as JAX reports them, or SystemExit when the
    devices are not TPUs or fewer than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n_chips}


def peak_memory(n_chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_chips])


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The contract's last line; ``checks`` (each compared number beside
    its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
