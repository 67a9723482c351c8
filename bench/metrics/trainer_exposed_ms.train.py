"""Device-idle time of the traced training window that the trainer's
host spans (``train/data``, ``train/dispatch``, ``train/fetch``,
``train/checkpoint``) cover, per step, in milliseconds: the host time
the chip waits for (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    att = spans.attribution(ctx)
    if att is None or not ctx["steps"]:
        return None
    t = sum(v for k, v in att["idle"].items() if k)
    return 1e3 * t / ctx["steps"] if t > 0 else None
