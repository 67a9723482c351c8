"""Share of the traced training window's busy device time spent moving
routed rows between cluster and sequence layout (``routing/scatter``:
the scatter-mean of the attention output and its transposed gather, and
the fused kernel's backward scatter-add of dq, dk and dv), forward,
remat recompute and backward: device seconds of the operations whose
``op_name`` holds the span (``bench/spans.py``), over busy time."""
from bench import spans

SPAN = "routing/scatter"


def read(ctx):
    att = spans.attribution(ctx)
    if att is None:
        return None
    t = spans.span_s(ctx["trace"].op_s, att["scopes"], SPAN)
    return 100.0 * t / ctx["trace"].busy_s if t > 0 else None
