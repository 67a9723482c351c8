"""Share of the roofline reached by the paged routing decode kernel: the
least time for the decode tokens of the traced sub-window,
max(FLOPs / bf16 peak, bytes / HBM bandwidth) (``counts
.paged_decode_counts``), over the kernel's device time there. The kernel
is the Pallas call (``tpu_custom_call``) that reads the cache's routing
pages, whose shape (slots, routing heads, clusters, page, dh) appears
among its operands."""
from bench import counts


def read(ctx):
    if ctx.get("mode") != "serve" or not ctx.get("decode_positions"):
        return None
    c, s = ctx["config"], ctx["serve"]
    cap = s["max_len"] // c["num_clusters"]
    page = (f"{c['dtype'].replace('bfloat16', 'bf16').replace('float32', 'f32')}"
            f"[{s['max_slots']},{c['routing_heads']},{c['num_clusters']},"
            f"{cap},{c['head_dim']}]")
    t = ctx["trace"].matching_s('custom_call_target="tpu_custom_call"', page)
    if t <= 0:
        return None
    flops, nbytes = counts.paged_decode_counts(c, ctx["decode_positions"],
                                               cap, ctx["elem_bytes"])
    t_flops = flops / ctx["peak"]["bf16_flops"]
    t_bytes = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"[paged decode] {t:.6f} s on the device; bound by "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'} (min {t_bytes:.6f} s "
        f"for {nbytes:.4g} B, {t_flops:.6f} s for {flops:.4g} FLOP)")
    return 100.0 * max(t_flops, t_bytes) / t
