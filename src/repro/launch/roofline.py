"""Roofline term extraction from a compiled (SPMD-partitioned) executable.

Hardware model: the target chip's peaks, looked up by ``device_kind``.

  compute term    = HLO_FLOPs_per_device / peak FLOP/s
  memory term     = HLO_bytes_per_device / peak HBM bytes/s
  collective term = collective_bytes_per_device / peak link bytes/s

(peaks per ``device_kind``: the PEAKS table)

cost_analysis() on the compiled executable is already per-partition (the
SPMD module of one device). collective_bytes comes from parsing the
optimized HLO: for each all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute we take the tensor shape, the replica-group
size n, and apply the ring model (bytes actually moved per device):

    all-gather       out_bytes * (n-1)/n
    all-reduce       2 * bytes * (n-1)/n
    reduce-scatter   out_bytes * (n-1)         (out is the scattered shard)
    all-to-all       bytes * (n-1)/n
    collective-permute  bytes

We also report the naive operand-byte sum (the assignment's literal recipe)
alongside — `collective_bytes_naive`.
"""
from __future__ import annotations

import dataclasses
import re

# Per-chip peaks keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM; 1,600 Gbit/s of
# interconnect over four ICI links = 50 GB/s per link). A device that is
# not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """(flops, hbm_bw, link_bw) peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\[([\d,]+)\](?:<=\[[\d,]+\])?")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",") if x]
        return dims[-1] if dims else default
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, n_devices: int) -> dict:
    """Scan optimized HLO for collectives; returns byte totals + op counts."""
    per_op: dict[str, dict[str, float]] = {}
    ring_bytes = 0.0
    naive_bytes = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done" in line.split("=")[0]:
            continue
        result_shape = m.group(1) or m.group(2)
        op = m.group(3)
        b = _shape_bytes(result_shape)
        n = max(_group_size(line, n_devices), 1)
        if op == "all-gather":
            moved = b * (n - 1) / n
        elif op == "all-reduce":
            moved = 2 * b * (n - 1) / n
        elif op == "reduce-scatter":
            moved = b * (n - 1)
        elif op == "all-to-all":
            moved = b * (n - 1) / n
        else:  # collective-permute
            moved = b
        ring_bytes += moved
        naive_bytes += b
        slot = per_op.setdefault(op, {"count": 0, "bytes": 0.0, "moved": 0.0})
        slot["count"] += 1
        slot["bytes"] += b
        slot["moved"] += moved
    return {"ring_bytes": ring_bytes, "naive_bytes": naive_bytes,
            "per_op": per_op}


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    device_kind: str            # target chip; keys the PEAKS table
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    collective_bytes: float     # per device, ring model
    collective_bytes_naive: float
    model_flops: float          # analytic 6ND (global, per step)
    memory_per_device: dict
    per_op: dict

    @property
    def t_compute(self):
        return self.hlo_flops / peaks(self.device_kind)["flops"]

    @property
    def t_memory(self):
        return self.hlo_bytes / peaks(self.device_kind)["hbm_bw"]

    @property
    def t_collective(self):
        return self.collective_bytes / peaks(self.device_kind)["link_bw"]

    @property
    def bottleneck(self):
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def useful_flops_ratio(self):
        tot = self.hlo_flops * self.n_devices
        return self.model_flops / tot if tot else 0.0

    @property
    def roofline_fraction(self):
        """Fraction of the dominant-term-bound step time that is useful
        compute: (model_flops / chips / peak) / max(term)."""
        ideal = (self.model_flops / self.n_devices
                 / peaks(self.device_kind)["flops"])
        t = max(self.t_compute, self.t_memory, self.t_collective)
        return ideal / t if t else 0.0

    def to_dict(self):
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def active_params(cfg) -> int:
    """Analytic ACTIVE parameter count (MoE: experts_per_token + shared)."""
    if cfg.n_experts == 0:
        return cfg.param_count()
    full = cfg.param_count()
    D, F = cfg.d_model, cfg.d_ff
    n_moe_blocks = sum(1 for b in cfg.pattern if b.ff == "moe") * cfg.n_groups
    inactive = (cfg.n_experts - cfg.experts_per_token) * 3 * D * F * n_moe_blocks
    return full - inactive


def model_flops(cfg, shape_name: str, seq: int, gbatch: int, kind: str) -> float:
    n = active_params(cfg)
    if kind == "train":
        return 6.0 * n * (seq * gbatch)
    if kind == "prefill":
        return 2.0 * n * (seq * gbatch)
    return 2.0 * n * gbatch  # decode: one token per sequence


def analyze(compiled, *, arch, shape, mesh_name, n_devices, device_kind, cfg,
            seq, gbatch, kind) -> Roofline:
    """Terms from the trip-count-aware HLO analysis (launch.hlo_analysis).

    XLA's own cost_analysis counts while bodies ONCE (a scan-over-layers
    model would be undercounted by its layer count!); we parse the optimized
    per-device SPMD module instead, multiplying by known trip counts."""
    from repro.launch.hlo_analysis import analyze_hlo

    mem = compiled.memory_analysis()
    memd = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            memd[k] = getattr(mem, k, 0)
    ca = compiled.cost_analysis() or {}
    memd["xla_flops_body_once"] = float(ca.get("flops", 0.0))
    a = analyze_hlo(compiled.as_text(), n_devices)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        device_kind=device_kind, hlo_flops=float(a["flops"]),
        hlo_bytes=float(a["hbm_bytes"]),
        collective_bytes=float(a["ring_bytes"]),
        collective_bytes_naive=float(a["naive_bytes"]),
        model_flops=model_flops(cfg, shape, seq, gbatch, kind),
        memory_per_device=memd,
        per_op=a["per_op"],
    )
