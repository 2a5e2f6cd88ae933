"""Model FLOPs of every token the traced job processed (each prompt, then
each generated token over its context) over the job's seconds times the
chip's bf16 peak."""
from bench import work

LAYER = "model step"
MOVES = "out_tok_s"


def read(ctx):
    if ctx.host_window_s <= 0 or not ctx.prompt_lens:
        return None
    flops = sum(work.request_flops(ctx.model, p, o)
                for p, o in zip(ctx.prompt_lens, ctx.output_lens))
    return 100.0 * flops / (ctx.host_window_s * ctx.peaks["bf16_flops_s"])
