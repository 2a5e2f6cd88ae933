"""Prefill's share of the chip's bf16 peak over the traced job: the model
FLOPs of the live prompt tokens (no bucket padding) over the device seconds
of the ``prefill`` programs and the ``kv_store`` programs that page their
KV into the pool, times the peak."""
from bench import programs, work

LAYER = "prefill"
MOVES = "out_tok_s"


def read(ctx):
    pt = programs.attributed(ctx)
    if pt is None or not ctx.prompt_lens:
        return None
    pf, store = pt.program_seconds("prefill"), pt.program_seconds("kv_store")
    if pf + store <= 0:
        return None
    flops = sum(work.prefill_flops(ctx.model, L) for L in ctx.prompt_lens)
    programs.log(f"prefill_mfu.serve: {flops!r} FLOP of "
                 f"{sum(ctx.prompt_lens)} prompt tokens; prefill programs "
                 f"{pf!r} s, kv_store programs {store!r} s")
    return 100.0 * flops / ((pf + store) * ctx.peaks["bf16_flops_s"])
