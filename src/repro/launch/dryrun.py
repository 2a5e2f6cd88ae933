import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, lower + compile the real
train/prefill/serve step against ShapeDtypeStruct stand-ins on the
production mesh — (16,16) single pod and (2,16,16) two pods — and record
memory_analysis / cost_analysis / collective schedule for the roofline.

Usage:
    python -m repro.launch.dryrun --arch all --shape all --mesh both \
        --out experiments/dryrun
    python -m repro.launch.dryrun --arch jamba_1_5_large --shape long_500k

NOTE: the XLA_FLAGS line above MUST run before any jax import (device count
locks on first init); keep it the first statement of this module.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, full_config, input_specs,
                           shape_is_applicable)
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (caches_sds, params_sds, rules_for,
                                    train_state_sds)
from repro.models import decode_step, prefill
from repro.models.config import ModelConfig
from repro.models.sharding import logical_rules
from repro.optim import AdamWConfig, CompressionConfig
from repro.train import make_train_step

# the chip the production meshes model (v5e pods); keys roofline.PEAKS
TARGET_KIND = "TPU v5 lite"


def _sharding_fn(mesh, rules):
    def fn(axes):
        spec = P(*(rules.get(a) if a is not None else None for a in axes))
        return NamedSharding(mesh, spec)

    return fn


def lower_cell(arch: str, shape_name: str, mesh, *, quantized_kv=False,
               cfg: ModelConfig | None = None, donate=True,
               optimized: bool = False):
    """Build + lower + compile one cell. Returns (compiled, meta).

    optimized=True turns on the beyond-paper perf knobs (EXPERIMENTS.md
    §Perf): bwd dtype cast, head-sharded attention, chunked attention."""
    import dataclasses

    cfg = cfg or full_config(arch)
    if optimized:
        cfg = dataclasses.replace(cfg, opt_bwd_cast=True, opt_head_shard=True,
                                  attn_impl="chunked")
    seq, gbatch, kind = SHAPES[shape_name]
    rules = rules_for(cfg, mesh, shape_name)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    n_dev = mesh.devices.size

    ocfg = AdamWConfig()
    ccfg = CompressionConfig(enabled=True)

    with logical_rules(rules, mesh):
        batch_sds = input_specs(cfg, shape_name,
                                sharding_fn=_sharding_fn(mesh, rules))
        if kind == "train":
            state_sds, _ = train_state_sds(cfg, ocfg, ccfg, mesh, rules)
            step = make_train_step(cfg, ocfg, ccfg)
            jf = jax.jit(step, donate_argnums=(0,) if donate else ())
            lowered = jf.lower(state_sds, batch_sds)
        elif kind == "prefill":
            psds, _ = params_sds(cfg, mesh, rules)
            csds, _ = caches_sds(cfg, gbatch, seq, mesh, rules,
                                 quantized_kv=quantized_kv)

            def prefill_step(params, batch, caches):
                return prefill(params, batch, cfg, caches)

            jf = jax.jit(prefill_step, donate_argnums=(2,) if donate else ())
            lowered = jf.lower(psds, batch_sds, csds)
        else:  # decode
            psds, _ = params_sds(cfg, mesh, rules)
            csds, _ = caches_sds(cfg, gbatch, seq, mesh, rules,
                                 quantized_kv=quantized_kv)

            def serve_step(params, caches, token, pos):
                logits, caches = decode_step(params, token, pos, caches, cfg)
                return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches

            tok = batch_sds["token"]
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jf = jax.jit(serve_step, donate_argnums=(1,) if donate else ())
            lowered = jf.lower(psds, csds, tok, pos)
        compiled = lowered.compile()

    meta = dict(arch=arch, shape=shape_name, mesh=mesh_name, kind=kind,
                seq=seq, global_batch=gbatch, n_devices=n_dev,
                quantized_kv=quantized_kv)
    return compiled, cfg, meta


def run_cell(arch: str, shape_name: str, mesh, out_dir: str | None, **kw):
    t0 = time.time()
    seq, gbatch, kind = SHAPES[shape_name]
    cfg = full_config(arch)
    ok, why = shape_is_applicable(cfg, shape_name)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if not ok:
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                   status="skipped", reason=why)
        _write(out_dir, tag, rec)
        print(f"SKIP  {tag}: {why}", flush=True)
        return rec
    try:
        compiled, cfg, meta = lower_cell(arch, shape_name, mesh, cfg=cfg, **kw)
        rl = RL.analyze(compiled, arch=arch, shape=shape_name,
                        mesh_name=mesh_name, n_devices=mesh.devices.size,
                        device_kind=TARGET_KIND, cfg=cfg, seq=seq,
                        gbatch=gbatch, kind=kind)
        rec = {**meta, **rl.to_dict(), "status": "ok",
               "compile_s": round(time.time() - t0, 1)}
        _write(out_dir, tag, rec)
        print(f"OK    {tag}: {rec['compile_s']}s "
              f"bottleneck={rl.bottleneck} "
              f"t=({rl.t_compute:.3e},{rl.t_memory:.3e},{rl.t_collective:.3e})s "
              f"useful={rl.useful_flops_ratio:.2f}", flush=True)
        return rec
    except Exception as e:
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                   status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        _write(out_dir, tag, rec)
        print(f"FAIL  {tag}: {type(e).__name__}: {str(e)[:200]}", flush=True)
        return rec


def _write(out_dir, tag, rec):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--quantized-kv", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(make_production_mesh(multi_pod=False))
    if args.mesh in ("multi", "both"):
        meshes.append(make_production_mesh(multi_pod=True))

    n_ok = n_fail = n_skip = 0
    for mesh in meshes:
        mesh_name = "x".join(str(s) for s in mesh.devices.shape)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"CACHED {tag} ({prev['status']})", flush=True)
                        n_ok += prev["status"] == "ok"
                        n_skip += prev["status"] == "skipped"
                        continue
                rec = run_cell(arch, shape, mesh, args.out,
                               quantized_kv=args.quantized_kv)
                n_ok += rec["status"] == "ok"
                n_fail += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"\nDRYRUN SUMMARY: ok={n_ok} skipped={n_skip} failed={n_fail}",
          flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
