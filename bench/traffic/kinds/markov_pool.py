"""Training batches: a pool of rows drawn from an order-1 Markov chain.

The chain's transition table comes from the seed: row ``i`` is the
softmax of ``logit_scale`` times i.i.d. Gumbel noise over the vocabulary,
so each token has a few likely successors and the loss can fall (a copy
of the repository's synthetic ``markov`` task, drawn on the device). The
pool holds ``pool_batches`` batches of ``rows_per_chip * chips`` rows of
``seq_len + 1`` tokens, all different, made in one jitted call; the
window cycles through it, so no step waits on the host.

Parameters (the traffic file): ``seq_len``, ``rows_per_chip``,
``pool_batches``, ``vocab``, ``logit_scale``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("rows", "length", "vocab",
                                             "scale"))
def markov_rows(key, *, rows: int, length: int, vocab: int, scale: float):
    """``rows`` sequences of ``length`` tokens, int32."""
    kt, k0, ks = jax.random.split(key, 3)
    logp = jax.nn.log_softmax(
        scale * jax.random.gumbel(kt, (vocab, vocab)), -1)
    first = jax.random.randint(k0, (rows,), 0, vocab, jnp.int32)

    def step(tok, k):
        nxt = jax.random.categorical(k, logp[tok]).astype(jnp.int32)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(ks, length - 1))
    return jnp.concatenate([first[:, None], rest.T], 1)


def make(traffic: dict, chips: int, key, sharding=None):
    """The pool: a list of ``pool_batches`` arrays of shape
    (rows_per_chip * chips, seq_len + 1), placed with ``sharding``."""
    rows = traffic["rows_per_chip"] * chips
    n = traffic["pool_batches"]
    toks = markov_rows(key, rows=rows * n, length=traffic["seq_len"] + 1,
                       vocab=traffic["vocab"],
                       scale=float(traffic["logit_scale"]))
    pool = [toks[i * rows:(i + 1) * rows] for i in range(n)]
    if sharding is not None:
        pool = [jax.device_put(b, sharding) for b in pool]
    return jax.block_until_ready(pool)
