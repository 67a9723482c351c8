"""Share of the roofline reached by the fused routing attention kernels
of training (forward, dq, dk/dv): the least time the chip could take for
the work they must do, max(FLOPs / bf16 peak, bytes / HBM bandwidth),
over their summed device time in the traced window. The kernels are the
operations named ``routed_attention_fused``."""
from bench import counts

KERNEL = "routed_attention_fused"


def read(ctx):
    if ctx.get("mode") != "train":
        return None
    t = ctx["trace"].kernel_s(KERNEL)
    if t <= 0:
        return None
    flops, nbytes = counts.routing_kernel_counts(
        ctx["config"], ctx["traffic"]["seq_len"],
        ctx["rows_per_chip"] * ctx["steps"], ctx["elem_bytes"])
    t_flops = flops / ctx["peak"]["bf16_flops"]
    t_bytes = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"[{KERNEL}] {t:.6f} s on the device; bound by "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'} "
        f"(min {t_bytes:.6f} s for {nbytes:.4g} B, {t_flops:.6f} s for "
        f"{flops:.4g} FLOP)")
    return 100.0 * max(t_flops, t_bytes) / t
