"""Share of the traced job the host spent outside the engine's decode
rounds and prefills: admission, harvest and mirror uploads."""
LAYER = "scheduler"
MOVES = "tpot_ms"
INSIDE = ("round", "prefill", "prefill_group")


def read(ctx):
    spans = sorted((s, e) for n, s, e in ctx.spans if n in INSIDE)
    if not spans or ctx.host_window_s <= 0:
        return None
    covered, end = 0.0, 0.0
    for s, e in spans:
        s, e = max(s, end), min(e, ctx.host_window_s)
        if e > s:
            covered += e - s
            end = e
    return 100.0 * (1.0 - covered / ctx.host_window_s)
