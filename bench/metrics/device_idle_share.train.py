"""Share of the traced training window in which no operation ran on the
chip (1 - union of device-operation intervals / window), mean over the
chips."""


def read(ctx):
    if ctx.get("mode") != "train":
        return None
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
