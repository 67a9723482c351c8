"""Mean wall time of the engine's batched decode step in the traced
sub-window: ``EngineMetrics.decode_time_s / decode_steps`` (host clock,
closing on the step's device sync)."""


def read(ctx):
    if ctx.get("mode") != "serve" or not ctx.get("decode_steps"):
        return None
    return 1000.0 * ctx["decode_time_s"] / ctx["decode_steps"]
