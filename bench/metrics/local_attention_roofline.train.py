"""Share of the roofline reached by the local window attention kernels
of training (forward, remat forward, dq, dk/dv): the least time the chip
could take for the work they must do, max(FLOPs / bf16 peak, bytes / HBM
bandwidth), over their summed device time in the traced window. The
kernels are the operations named ``local_attention`` (a Pallas call
takes its jitted wrapper's name).

FLOPs are the causal local pairs (``counts.local_pairs_per_token``) at
4·dh a pair, three times for forward and backward. Bytes are what the
algorithm reads and writes once: the forward reads q, k and v and writes
the output and its log-sum-exps; the backward reads q, k, v, the output's
cotangent, the log-sum-exps and the softmax row sums, and writes dq, dk
and dv. A note gives the kernels' time beside the device time of the
``kernels/local_attention`` span, which also holds the wrapper's XLA
work (the row sums, reshapes)."""
from bench import counts, spans

KERNEL = "local_attention"
SPAN = "kernels/local_attention"


def kernel_counts(c, n: int, sequences: int, elem_bytes: int):
    """FLOPs and bytes of the local attention kernels of one training
    step over ``sequences`` rows of ``n`` tokens, summed over layers and
    local heads. Returns (flops, bytes)."""
    dh = c["head_dim"]
    calls = sequences * c["num_layers"] * (c["num_heads"]
                                           - c["routing_heads"])
    pairs = counts.local_pairs_per_token(n, c["local_window"]) * n
    flops = 3 * 4 * dh * pairs
    plane = n * dh * elem_bytes            # q, k, v, out or a gradient
    rows = n * 4                           # float32 row stats
    fwd = 3 * plane + plane + rows
    bwd = 4 * plane + 2 * rows + 3 * plane
    return calls * flops, calls * (fwd + bwd)


def read(ctx):
    if ctx.get("mode") != "train":
        return None
    t = ctx["trace"].kernel_s(KERNEL)
    if t <= 0:
        return None
    flops, nbytes = kernel_counts(
        ctx["config"], ctx["traffic"]["seq_len"],
        ctx["rows_per_chip"] * ctx["steps"], ctx["elem_bytes"])
    t_flops = flops / ctx["peak"]["bf16_flops"]
    t_bytes = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    att = spans.attribution(ctx)
    span = "" if att is None else (
        f"; span {SPAN} "
        f"{spans.span_s(ctx['trace'].op_s, att['scopes'], SPAN):.6f} s")
    ctx["notes"].append(
        f"[{KERNEL}] {t:.6f} s on the device{span}; bound by "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'} "
        f"(min {t_bytes:.6f} s for {nbytes:.4g} B, {t_flops:.6f} s for "
        f"{flops:.4g} FLOP)")
    return 100.0 * max(t_flops, t_bytes) / t
