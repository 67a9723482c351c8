"""Plain reference of a Routing Transformer language model, computed in
blocks: the equations, weights and training of ``refs/routing_lm.py``
(whose docstring states them), arranged so that a step at long sequences
and wide windows fits one chip. It imports nothing of the program under
test.

What differs is only the order of the work, never its arithmetic
(float32, every matmul at ``Precision.HIGHEST``):

* local heads: one (head, query block) at a time, each block's causal
  softmax over its own and the previous block's 2w keys under
  ``jax.checkpoint``; the whole-tensor form holds (B, H, nb, w, 2w)
  scores, 1.6 GB a row at w = 2048;
* routing heads: one head at a time under ``jax.checkpoint``, by
  ``routing_lm.routing_attention`` itself (a head's (B, k, w, w) scores
  are 151 MB at two rows of 12288 in 8 clusters);
* training: the step donates the weights and Adam's moments, returns the
  gradient's leaf norms rather than the gradient, and the starting
  weights are made again from the seed at the end instead of being kept.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.refs import routing_lm as base
from bench.refs.routing_lm import (NEG, centroid_update, dropout, frozen,
                                   init_params, layer_norm, leaf_gap_norms,
                                   leaf_norms, learning_rate, make_params, mm,
                                   rope)

# what bench/modes/train.py calls of a reference
__all__ = ["init_params", "leaf_gap_norms", "leaf_norms", "train"]


def local_attention(q, k, v, window):
    """``routing_lm.local_attention``, one (head, query block) at a time.
    q, k, v: (B, H, N, dh), N % window == 0."""
    B, H, N, dh = q.shape
    nb, w = N // window, window
    qb, kb, vb = (a.reshape(B, H, nb, w, dh) for a in (q, k, v))
    prev = lambda a: jnp.concatenate([jnp.zeros_like(a[:, :, :1]),
                                      a[:, :, :-1]], 2)
    kc = jnp.concatenate([prev(kb), kb], 3)             # (B,H,nb,2w,dh)
    vc = jnp.concatenate([prev(vb), vb], 3)
    # (head, block) pairs first: (H*nb, B, rows, dh)
    first = lambda a: a.transpose(1, 2, 0, 3, 4).reshape(
        H * nb, B, a.shape[3], dh)
    blocks = jnp.tile(jnp.arange(nb), H)

    @jax.checkpoint
    def one(xs):
        qi, ki, vi, b = xs
        s = mm("bqd,bkd->bqk", qi, ki) / jnp.sqrt(jnp.float32(dh))
        qpos = b * w + jnp.arange(w)
        kpos = b * w - w + jnp.arange(2 * w)
        keep = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
        p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
        return mm("bqk,bkd->bqd", p, vi)

    o = jax.lax.map(one, (first(qb), first(kc), first(vc), blocks))
    return o.reshape(H, nb, B, w, dh).transpose(2, 0, 1, 3, 4).reshape(
        B, H, N, dh)


def routing_attention(q, v, mu, num_clusters):
    """``routing_lm.routing_attention``, one head at a time. q, v: (B,
    Hr, N, dh); mu (Hr, k, dh). Returns the outputs and the routing
    vectors."""
    @jax.checkpoint
    def one(xs):
        qh, vh, muh = xs
        o, r = base.routing_attention(qh[:, None], vh[:, None], muh[None],
                                      num_clusters)
        return o[:, 0], r[:, 0]

    heads = lambda a: a.transpose(1, 0, 2, 3)
    o, r = jax.lax.map(one, (heads(q), heads(v), mu))
    return heads(o), heads(r)


def forward(p, mu, tokens, c, drop_key=None):
    """``routing_lm.forward`` with the blocked attention."""
    H, dh, hr = c["num_heads"], c["head_dim"], c["routing_heads"]
    hl = H - hr
    B, N = tokens.shape
    x = p["tok"].astype(jnp.float32)[tokens]
    layers = {n: p[n] for n in ("ln1_scale", "ln1_bias", "wq", "wk", "wv",
                                "wo", "ln2_scale", "ln2_bias", "w_up",
                                "w_down")}

    def heads(a):
        return a.reshape(B, N, H, dh).transpose(0, 2, 1, 3)

    @jax.checkpoint
    def layer(x, xs):
        w, mu_l, l = xs
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        h = layer_norm(x, w["ln1_scale"], w["ln1_bias"])
        q, k, v = (heads(mm("bnd,de->bne", h, w[n]))
                   for n in ("wq", "wk", "wv"))
        o_l = local_attention(rope(q[:, :hl], c["rope_theta"]),
                              rope(k[:, :hl], c["rope_theta"]), v[:, :hl],
                              c["local_window"])
        o_r, r = routing_attention(q[:, hl:], v[:, hl:], mu_l,
                                   c["num_clusters"])
        o = jnp.concatenate([o_l, o_r], 1).transpose(0, 2, 1, 3)
        a = mm("bne,ed->bnd", o.reshape(B, N, H * dh), w["wo"])
        keys = (None, None)
        if drop_key is not None:
            keys = jax.random.split(
                jax.random.fold_in(jax.random.fold_in(drop_key, 0), l), 2)
        x = x + dropout(a, keys[0], c["dropout"])
        h2 = layer_norm(x, w["ln2_scale"], w["ln2_bias"])
        f = mm("bnf,fd->bnd", jax.nn.relu(mm("bnd,df->bnf", h2, w["w_up"])),
               w["w_down"])
        x = x + dropout(f, keys[1], c["dropout"])
        return x, centroid_update(mu_l, r, c["decay"])

    x, new_mu = jax.lax.scan(layer, x,
                             (layers, mu, jnp.arange(c["num_layers"])))
    x = layer_norm(x, p["lnf_scale"].astype(jnp.float32),
                   p["lnf_bias"].astype(jnp.float32))
    return mm("bnd,dv->bnv", x, p["unembed"].astype(jnp.float32)), new_mu


def loss_fn(p, mu, batch, c, drop_key):
    """Token-mean next-token cross entropy of rows ``batch`` (B, N+1)."""
    logits, new_mu = forward(p, mu, batch[:, :-1], c, drop_key)
    tgt = batch[:, 1:]
    lse = jax.nn.logsumexp(logits, -1)
    nll = lse - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return nll.mean(), new_mu


@functools.partial(jax.jit, static_argnames=("c", "t"),
                   donate_argnums=(0, 1, 2))
def _train_step(p, mu, opt, batch, drop_key, lr, *, c, t):
    """``routing_lm``'s step (clip, Adam) on the blocked loss; returns
    the clipped gradient's leaf norms in place of the gradient."""
    c, t = dict(c), dict(t)
    (loss, new_mu), g = jax.value_and_grad(loss_fn, has_aux=True)(
        p, mu, batch, c, drop_key)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"]
                                               / jnp.maximum(gn, 1e-9)), g)
    b1, b2 = t["betas"]
    n = opt["count"] + 1
    m = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, opt["m"], g)
    v = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, opt["v"], g)
    bc1 = 1 - b1 ** n.astype(jnp.float32)
    bc2 = 1 - b2 ** n.astype(jnp.float32)
    p = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + t["eps"]),
        p, m, v)
    norms = {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in g.items()}
    return p, new_mu, {"m": m, "v": v, "count": n}, loss, norms


def train(key, batches, c, t, train_seed: int):
    """``routing_lm.train`` on the blocked step: the losses, the per-leaf
    norms of step 1's clipped gradient, and the per-leaf norms of the
    weights' change over all the steps."""
    p, mu = make_params(key, c=frozen(c))
    opt = {"m": jax.tree.map(jnp.zeros_like, p),
           "v": jax.tree.map(jnp.zeros_like, p),
           "count": jnp.zeros((), jnp.int32)}
    root = jax.random.PRNGKey(train_seed)
    losses, g1 = [], None
    for s, batch in enumerate(batches):
        drop = jax.random.fold_in(root, s) if c["dropout"] > 0 else None
        p, mu, opt, loss, gn = _train_step(
            p, mu, opt, batch, drop, learning_rate(s + 1, t),
            c=frozen(c), t=frozen(t))
        losses.append(float(loss))
        if g1 is None:
            g1 = jax.device_get(gn)
    del opt, mu
    dp = jax.device_get(leaf_gap_norms(p, make_params(key, c=frozen(c))[0]))
    return losses, g1, dp
