"""Serving cells: ``serve.engine.InferenceEngine`` at its defaults (one
jitted prefill per prompt length, the cluster-paged cache, no prefix
cache), driven open-loop by the traffic's requests.

Set-up makes the weights from the seed (the reference's generator, cast
to the configuration's dtype, in the program's layout), builds the engine
and serves the warm-up requests, which compile every prompt shape of the
mix and both decode paths. The window then submits each request when it
is due, steps the engine whenever it has work, and stamps every output
token with the time the step that produced it returned. Arrivals stop at
the window's end; the requests then drain.

* ``serve_ttft_p95_s``: 95th percentile, nearest rank, over the requests
  due in the window, of the time from when each was due to its first
  token (a request that never got one counts as infinite);
* ``serve_itl_p95_s``: 95th percentile over every gap between
  consecutive output tokens of those requests (the first token and the
  first decoded one come out of the same engine step, a gap of ~0);
* ``serve_output_tokens_per_s``: output tokens stamped inside the window
  over the window.

Correctness: after the drain the engine is freed and a sample of the
finished greedy requests, drawn from the seed and holding the longest,
is run through the reference's serving forward (``serve_forward``), and
``served_logit_gap`` is the widest gap by which a served token's
reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import importlib
import math
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.modes.train import program_config, program_params
from bench.refs.routing_lm import seed_key

TRACE_SECONDS = 5.0


def nearest_rank(values, q):
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)] if v else math.inf


def run(ctx, fault: Optional[Callable] = None) -> dict:
    """One run of a serving cell. ``fault`` (tests and fault readings
    only) alters the engine before the window."""
    from repro.serve.engine import InferenceEngine, Request
    from repro.serve.engine.sampling import SamplingParams

    c, s, t = ctx.config["model"], ctx.config["serve"], ctx.traffic
    ref = importlib.import_module(f"bench.refs.{ctx.config['reference']}")
    kind = importlib.import_module(f"bench.traffic.kinds.{t['kind']}")
    dtype = jnp.dtype(c["dtype"])
    key = seed_key(ctx.seed)
    sample_seed = ctx.seed % (2 ** 31)

    params, kstate = jax.jit(lambda k: program_params(
        *ref.init_params(k, c), dtype))(key)
    eng = InferenceEngine(program_config(c), params, kstate,
                          max_slots=s["max_slots"], max_len=s["max_len"])
    del params
    ctx.note(f"[serve] attn_backends={eng.attn_backends}")
    if not all(s["decode_impl"] in b for b in eng.attn_backends.values()):
        raise RuntimeError(f"decode does not resolve to {s['decode_impl']}: "
                           f"{eng.attn_backends}")

    def request(r):
        return Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new,
                       sampling=SamplingParams(temperature=r.temperature,
                                               seed=sample_seed))

    ctx.note(f"[setup] engine built: {ctx.since_start():.1f} s")
    warm = kind.warm(t, ctx.seed)
    for greedy in (False, True):   # both decode paths, every prompt shape
        eng.run([request(r) for r in warm
                 if (r.temperature <= 0) == greedy])
    if fault is not None:
        fault(eng)

    reqs = sorted(kind.make(t, ctx.seconds, ctx.seed), key=lambda r: r.due_s)
    live, stamps, late = {}, {r.uid: [] for r in reqs}, []
    served = {}
    # --trace 1: profile TRACE_SECONDS in the middle of the arrivals
    trace_at = ctx.seconds / 2 - TRACE_SECONDS / 2 if ctx.trace else None
    traced, opened = None, None
    ctx.compiles.reset()
    t0 = time.perf_counter()
    setup_s = ctx.setup_s(t0)
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(reqs) and reqs[i].due_s <= now:
            r = reqs[i]
            served[r.uid] = eng.submit(request(r))
            live[r.uid] = r
            late.append(now - r.due_s)
            i += 1
        if trace_at is not None and opened is None and traced is None \
                and now >= trace_at:
            ctx.trace_start()
            opened = (now, _engine_counts(eng))
        if opened is not None and now >= trace_at + TRACE_SECONDS:
            ctx.trace_stop()
            traced = (opened[0], now, opened[1], _engine_counts(eng))
            opened = None
        if eng.has_work():
            eng.step()
            now = time.perf_counter() - t0
            for uid in list(live):
                out = served[uid].output
                got = stamps[uid]
                got.extend([now] * (len(out) - len(got)))
                if served[uid].state in ("finished", "cancelled"):
                    del live[uid]
        elif i < len(reqs):
            time.sleep(max(0.0, reqs[i].due_s - now))
        else:
            break
    if opened is not None:
        now = time.perf_counter() - t0
        ctx.trace_stop()
        traced = (opened[0], now, opened[1], _engine_counts(eng))
    drained = time.perf_counter() - t0
    window_compiles = ctx.compiles.count
    memory_peak = ctx.memory_peak()
    ctx.note(f"[serve] {len(reqs)} requests; generator late p95 "
             f"{nearest_rank(late, 0.95):.6f} s, max {max(late):.6f} s")

    ttft, gaps, in_window, failed = [], [], 0, 0
    for r in reqs:
        st = stamps[r.uid]
        ttft.append(st[0] - r.due_s if st else math.inf)
        gaps.extend(np.diff(st).tolist())
        in_window += sum(1 for x in st if x <= ctx.seconds)
        failed += len(st) < r.max_new
    layer = {"mode": "serve", "config": c, "traffic": t, "serve": s,
             "chips": ctx.chips, "elem_bytes": dtype.itemsize}
    if traced is not None:
        layer.update(_traced_work(reqs, stamps, *traced))

    outputs = {r.uid: list(served[r.uid].output) for r in reqs}
    eng.close()
    del eng, served
    gc.collect()
    gap, mean_gap = _served_gap(ctx, ref, c, s, t, reqs, outputs, dtype,
                                key)
    return {
        "metrics": {"serve_ttft_p95_s": nearest_rank(ttft, 0.95),
                    "serve_itl_p95_s": nearest_rank(gaps, 0.95),
                    "serve_output_tokens_per_s": in_window / ctx.seconds,
                    "setup_s": setup_s},
        "checks": {"served_logit_gap": gap},
        "attempted": len(reqs),
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "window_compiles": window_compiles,
        "layer_ctx": layer,
        "diag": {"ttft_p50_s": nearest_rank(ttft, 0.5),
                 "ttft_p50_last_quarter_s": nearest_rank(
                     ttft[-max(1, len(ttft) // 4):], 0.5),
                 "generator_late_p95_s": nearest_rank(late, 0.95),
                 "drain_s": drained - ctx.seconds,
                 "served_gap_mean": mean_gap},
    }


def _engine_counts(eng):
    m = eng.metrics
    return (m.decode_steps, m.decode_time_s, m.prefill_tokens,
            m.prefill_time_s)


def _traced_work(reqs, stamps, a, b, m0, m1):
    """What the engine did inside the traced sub-window [a, b): decode
    tokens (with the position each was computed at) and its counters."""
    positions = []
    for r in reqs:
        for j, x in enumerate(stamps[r.uid]):
            if j >= 1 and a <= x < b:
                positions.append(len(r.prompt) + j - 1)
    return {"decode_positions": positions,
            "decode_steps": m1[0] - m0[0], "decode_time_s": m1[1] - m0[1],
            "prefill_tokens": m1[2] - m0[2],
            "prefill_time_s": m1[3] - m0[3]}


def _served_gap(ctx, ref, c, s, t, reqs, outputs, dtype, key):
    """Widest reference-logit gap of the served greedy tokens over a
    sample of finished greedy requests (the longest always in it)."""
    done = [r for r in reqs if r.temperature <= 0
            and len(outputs[r.uid]) == r.max_new]
    if not done:
        return math.inf, math.inf
    done.sort(key=lambda r: -(len(r.prompt) + r.max_new))
    rng = np.random.default_rng(ctx.seed)
    rest = rng.permutation(len(done) - 1)[:t["check_requests"] - 1] + 1
    sample = [done[0]] + [done[i] for i in sorted(rest)]
    p, mu = ref.make_params(jax.device_put(key, ctx.devices[0]),
                            c=ref.frozen(c))
    p = {n: a.astype(dtype) for n, a in p.items()}
    widest, total, n_tok, agree = 0.0, 0.0, 0, 0
    for r in sample:
        out = outputs[r.uid]
        toks = np.zeros(s["max_len"], np.int32)
        seq = list(r.prompt) + out[:-1]
        toks[:len(seq)] = seq
        served = np.full(t["output"]["max"], -1, np.int32)
        served[:len(out)] = out
        g = ref.served_token_gaps(p, mu, jnp.asarray(toks),
                                  jnp.asarray(served), c=ref.frozen(c),
                                  prompt_len=len(r.prompt),
                                  cap=s["max_len"] // c["num_clusters"],
                                  rounding=ctx.config.get("control_rounding"))
        g = np.asarray(g)[:len(out)]
        widest = max(widest, float(g.max()))
        total, n_tok = total + float(g.sum()), n_tok + len(out)
        agree += int((g == 0).sum())
    ctx.note(f"[serve] compared {n_tok} served tokens of {len(sample)} "
             f"greedy requests: widest gap {widest}, mean gap "
             f"{total / n_tok}, reference's best {agree / n_tok:.4f}")
    return widest, total / n_tok
