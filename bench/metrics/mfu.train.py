"""Model FLOP/s utilization of training: (6 FLOPs per parameter plus
attention, forward and backward, no recomputation) per token, times the
tokens per second of the traced window, over the chips' bf16 peak."""
from bench import counts


def read(ctx):
    if ctx.get("mode") != "train" or not ctx["steps"]:
        return None
    flops = counts.train_flops_per_token(ctx["config"],
                                         ctx["traffic"]["seq_len"])
    tokens_per_s = ctx["tokens"] / ctx["trace"].window_s
    return 100.0 * flops * tokens_per_s / (ctx["chips"]
                                           * ctx["peak"]["bf16_flops"])
