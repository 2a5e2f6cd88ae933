"""The KV write's share of its roofline over the traced job: the least
time to read the bf16 keys and values and write their packed codes and
scales, for every live token written (each prompt token, then each kept
decode token: ``slots`` events' ``kv_written``), over the device seconds of
the ``quantize_packed`` ops in every program plus the ``kv_store`` programs
(the prefill cache's pages copied into the pool; the ``kv_move`` programs
copy KV already written, and are left out)."""
from collections import defaultdict

from bench import programs, work

LAYER = "kv write"
MOVES = "out_tok_s"
KERNEL_NAMES = ("quantize_packed",)
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def is_kernel(name: str) -> bool:
    return any(k in name for k in KERNEL_NAMES)


def read(ctx):
    pt = programs.attributed(ctx)
    rounds = [r for r in ctx.rounds if "kv_written" in r]
    if pt is None or not rounds or not ctx.prompt_lens:
        return None
    split = defaultdict(float)        # quantize seconds by program layer
    for ops in pt.devices.values():
        for op in ops:
            if is_kernel(op.name):
                split[op.layer] += op.dur / 1e9 / len(pt.devices)
    store = pt.program_seconds("kv_store")
    t = sum(split.values()) + store
    if t <= 0:
        return None
    m = ctx.model
    tokens = sum(ctx.prompt_lens) + sum(int(r["kv_written"]) for r in rounds)
    read_bytes = (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
                  * m["head_dim"] * DTYPE_BYTES[m["torch_dtype"]])
    nbytes = tokens * (read_bytes + work.kv_bytes_per_token(m, ctx.kv_bits))
    programs.log("kv_write_roofline.serve: quantize_packed s by program "
                 + ", ".join(f"{k} {v!r}" for k, v in sorted(split.items()))
                 + f"; kv_store programs {store!r} s; {tokens} tokens, "
                 f"{nbytes} B")
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_s"] / t
