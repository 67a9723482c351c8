"""Share of the traced serving sub-window in which no operation ran on
the chip (1 - union of device-operation intervals / window)."""


def read(ctx):
    if ctx.get("mode") != "serve" or "decode_steps" not in ctx:
        return None
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
