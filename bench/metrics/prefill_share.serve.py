"""Share of the traced job inside the engine's prefill calls."""
LAYER = "prefill"
MOVES = "out_tok_s"


def read(ctx):
    spans = [(s, e) for n, s, e in ctx.spans
             if n in ("prefill", "prefill_group")]
    if not spans or ctx.host_window_s <= 0:
        return None
    return 100.0 * sum(e - s for s, e in spans) / ctx.host_window_s
