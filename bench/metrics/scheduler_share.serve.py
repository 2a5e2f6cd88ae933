"""Share of the traced job the engine's host loop spent scheduling, where
the work happens: inside its ``schedule``, ``round.prep`` and ``harvest``
phases, less the prefills nested in them."""
from bench import programs

LAYER = "scheduler"
MOVES = "tpot_ms"
PHASES = ("schedule", "round.prep", "harvest")
NESTED = ("prefill", "prefill_group")


def read(ctx):
    pt = programs.of(ctx)
    if pt is None or pt.window_s <= 0 or not any(
            n in PHASES for n, _, _ in pt.host_spans):
        return None
    parts = {n: pt.phase_seconds((n,), minus=NESTED if n in
                                 ("schedule", "harvest") else ())
             for n in programs.LEAVES}
    programs.log("scheduler_share.serve: host s by phase "
                 + ", ".join(f"{k} {v!r}" for k, v in parts.items())
                 + f" of {pt.window_s!r}; longest idle gaps "
                 f"{pt.idle_gaps(10)}")
    return 100.0 * pt.phase_seconds(PHASES, minus=NESTED) / pt.window_s
