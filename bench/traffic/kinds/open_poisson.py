"""Serving traffic: independent users arriving open-loop.

Every seed gets the same work in another order. The ``n = rate *
seconds`` requests take their prompt and output lengths from evenly
spaced quantiles of two log-normal distributions, and their gaps from
evenly spaced quantiles of the exponential distribution of a Poisson
process at ``rate_per_s``; the seed only shuffles which request comes
when and draws the prompts' tokens. So runs with different seeds do the
same set of prefills and decodes, and differ only in order and content.

Prompt lengths are rounded up to a multiple of ``prompt.round`` and
clipped to [``prompt.min``, ``prompt.max``], so the server sees a fixed,
small set of prompt shapes; output lengths are clipped to
[``output.min``, ``output.max``]. Every ``greedy_every``-th request (in
the unshuffled order) decodes greedily; the rest sample at
``temperature``. ``warm`` lists the requests run in set-up, before the
window: one per prompt shape.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Req:
    uid: int
    due_s: float            # seconds after the window opens
    prompt: List[int]
    max_new: int
    temperature: float


def _lognormal(q, median, sigma):
    return median * math.exp(sigma * NormalDist().inv_cdf(q))


def prompt_shapes(t: dict) -> List[int]:
    """Every prompt length the mix can send."""
    pr = t["prompt"]
    return list(range(pr["min"], pr["max"] + 1, pr["round"]))


def sizes(t: dict, n: int):
    """(prompt_len, max_new, greedy) of ``n`` requests, unshuffled."""
    pr, out = t["prompt"], t["output"]
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [min(pr["max"], max(pr["min"], pr["round"] * math.ceil(
        _lognormal(q, pr["median"], pr["sigma"]) / pr["round"])))
        for q in qs]
    outs = [min(out["max"], max(out["min"], round(
        _lognormal(q, out["median"], out["sigma"])))) for q in qs]
    # pair prompt and output quantiles by a fixed permutation, the same
    # for every seed: long prompts do not always get long answers
    perm = np.random.default_rng(t["pairing_seed"]).permutation(n)
    return [(prompts[i], outs[perm[i]], i % t["greedy_every"] == 0)
            for i in range(n)]


def make(t: dict, seconds: float, seed: int) -> List[Req]:
    """The requests due in a window of ``seconds``."""
    n = max(1, round(t["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    gaps = [-math.log(1 - (i + 0.5) / n) / t["rate_per_s"] for i in range(n)]
    gaps = [gaps[i] for i in rng.permutation(n)]
    scale = seconds / sum(gaps)         # the last request is due at the end
    due = np.cumsum(gaps) * scale - gaps[0] * scale
    base = sizes(t, n)
    reqs = []
    for k, i in enumerate(order):
        plen, new, greedy = base[i]
        reqs.append(Req(uid=k, due_s=float(due[k]),
                        prompt=rng.integers(0, t["vocab"], plen).tolist(),
                        max_new=new,
                        temperature=0.0 if greedy else t["temperature"]))
    return reqs


def warm(t: dict, seed: int) -> List[Req]:
    """Set-up traffic: one request per prompt shape, sampled and greedy."""
    rng = np.random.default_rng(seed)
    return [Req(uid=1_000_000 + k, due_s=0.0,
                prompt=rng.integers(0, t["vocab"], plen).tolist(),
                max_new=t["warm_new_tokens"],
                temperature=0.0 if k % 2 else t["temperature"])
            for k, plen in enumerate(prompt_shapes(t))]
