"""Faults planted under a cell's timed path, to show that the
correctness comparison catches them. Never used by a benchmark run; the
tests and ``calibrate.py`` plant them. A training fault wraps the
program's step function; a serving fault alters the engine."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def frozen(step):
    """A step that returns its state unchanged (only the counter moves)."""
    def f(ts, batch):
        keep = jax.tree.map(jnp.copy, ts)
        new, metrics = step(ts, batch)
        return keep._replace(step=new.step), metrics
    return f


def half_batch(step):
    """A step that leaves out half of the batch and takes the mean over
    the rest."""
    def f(ts, batch):
        toks = batch["tokens"]
        return step(ts, {"tokens": toks[: toks.shape[0] // 2]})
    return f


def altered_token(eng):
    """Every decoded token replaced by the next token id where the
    engine produces it (both decode paths: all-greedy and mixed)."""
    def shifted(decode):
        def f(*args):
            toks, logits, pool = decode(*args)
            return (toks + 1) % logits.shape[-1], logits, pool
        return f
    eng._decode_greedy = shifted(eng._decode_greedy)
    eng._decode_sample = shifted(eng._decode_sample)


FAULTS = {"train": {"frozen": frozen, "half_batch": half_batch},
          "serve": {"altered_token": altered_token}}
