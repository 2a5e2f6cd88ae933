"""The decode round's share of its roofline over the traced job: the least
time the chip could take for the steps that kept a token (each reads the
matmul weights and the head once, and the packed KV of the context every
kept token attended), over the device seconds of the ``round`` programs.
The kept work is the engine's own count (``slots`` events: ``steps_kept``,
``kv_live``, ``kv_written``); the programs are found by the engine's
``PROGRAMS`` table in the profile's ``XLA Modules`` line."""
from bench import programs, work

LAYER = "decode round"
MOVES = "tpot_ms"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    pt = programs.attributed(ctx)
    rounds = [r for r in ctx.rounds if "kv_live" in r]
    if pt is None or not rounds:
        return None
    t = pt.program_seconds("round")
    if t <= 0:
        return None
    m, pk = ctx.model, ctx.peaks
    params = (m["num_hidden_layers"] * work.layer_matmul_params(m)
              + work.head_params(m))
    weights = params * DTYPE_BYTES[m["torch_dtype"]]
    least = 0.0
    for r in rounds:
        nbytes = (r["steps_kept"] * weights + r["kv_live"]
                  * work.kv_bytes_per_token(m, ctx.kv_bits))
        ops = (2 * params * r["kv_written"]
               + work.attention_flops(m, int(r["kv_live"])))
        least += max(nbytes / pk["hbm_bytes_s"], ops / pk["bf16_flops_s"])
    layers = {k: pt.program_seconds(k)
              for k in ("round", "prefill", "kv_store", "kv_move", "upload",
                        "other")}
    programs.log(
        "round_roofline.serve: device s by program "
        + ", ".join(f"{k} {v!r}" for k, v in layers.items())
        + f"; {100 * pt.attributed_share()!r}% of op time in PROGRAMS; "
        f"least {least!r} s over {sum(r['steps_kept'] for r in rounds)!r} "
        f"kept steps; top ops {pt.top_ops(10)}")
    return 100.0 * least / t
