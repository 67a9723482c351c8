"""Engine prefill time per thousand prompt tokens in the traced
sub-window: ``EngineMetrics.prefill_time_s / prefill_tokens`` (host
clock, closing on the first token's sampling)."""


def read(ctx):
    if ctx.get("mode") != "serve" or not ctx.get("prefill_tokens"):
        return None
    return 1e6 * ctx["prefill_time_s"] / ctx["prefill_tokens"]
