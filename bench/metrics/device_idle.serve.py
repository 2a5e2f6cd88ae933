"""Share of the traced job in which no operation ran on the device."""
LAYER = "device"
MOVES = "tpot_ms"


def read(ctx):
    d = ctx.device
    if not d.devices or d.window_s <= 0:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
