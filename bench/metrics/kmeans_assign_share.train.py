"""Share of the traced training window's busy device time spent in the
routing assignment (``routing/assign``: routing-vector normalization,
centroid scores, the balanced top-k and its sort), forward, remat
recompute and backward: device seconds of the operations whose
``op_name`` holds the span (``bench/spans.py``), over busy time."""
from bench import spans

SPAN = "routing/assign"


def read(ctx):
    att = spans.attribution(ctx)
    if att is None:
        return None
    t = spans.span_s(ctx["trace"].op_s, att["scopes"], SPAN)
    return 100.0 * t / ctx["trace"].busy_s if t > 0 else None
