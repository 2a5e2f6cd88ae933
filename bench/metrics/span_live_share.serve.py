"""Share of the KV positions the decode rounds' attention covered that were
live context: over the traced job, the kept tokens' attended context
(``slots`` events' ``kv_live``) over what the attention calls covered for
those steps (``kv_attended``: every slot row over the round's span bucket).
The rest is padding past each row's length and rows with no request."""
LAYER = "decode round"
MOVES = "tpot_ms"


def read(ctx):
    rounds = [r for r in ctx.rounds if "kv_attended" in r]
    attended = sum(int(r["kv_attended"]) for r in rounds)
    if attended <= 0:
        return None
    return 100.0 * sum(int(r["kv_live"]) for r in rounds) / attended
