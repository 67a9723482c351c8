"""Model FLOP/s utilization of decoding: the forward FLOPs of the tokens
decoded in the traced sub-window (``counts.decode_flops``) over the
engine's summed decode-step wall time there (host clock closing on the
step's device sync) times the chips' bf16 peak."""
from bench import counts


def read(ctx):
    if ctx.get("mode") != "serve" or not ctx.get("decode_steps"):
        return None
    cap = ctx["serve"]["max_len"] // ctx["config"]["num_clusters"]
    flops = counts.decode_flops(ctx["config"], ctx["decode_positions"], cap)
    return 100.0 * flops / (ctx["decode_time_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
