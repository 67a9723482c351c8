"""Plain reference of a Routing Transformer language model (Roy et al.
2020), in jax.numpy and float32 with every matmul at
``Precision.HIGHEST``. It imports nothing of the program under test.

What it computes, per layer, for a ``local+routing`` model with ``H``
heads of ``dh`` split into ``Hl`` local heads (first) and ``Hr`` routing
heads (last):

* pre-norm LayerNorm (eps 1e-6), q/k/v projections;
* local heads: rotary position embedding (interleaved pairs, base
  ``rope_theta``) on q and k, then causal attention in blocks of
  ``window`` tokens, each query block seeing its own block and the one
  before it;
* routing heads: shared-QK routing vectors ``r = LN(q)`` without scale
  or bias; affinities ``r . mu`` to ``k`` centroids; balanced membership
  (each centroid takes its ``w = N / k`` highest-affinity tokens, in
  sequence order); causal softmax attention of ``r`` against ``r`` inside
  each cluster on values ``v``; each token's output is the mean over the
  clusters that hold it (0 if none);
* centroids: exponential moving average (decay ``d``) towards the mean of
  the routing vectors whose arg-max centroid they are, over the batch;
  empty clusters keep theirs;
* output projection, dropout, residual; pre-norm ReLU MLP, dropout,
  residual;

then a final LayerNorm, the untied output head, and the token-mean
cross entropy. Training adds global-norm gradient clipping, Adam and the
linear-warm-up, inverse-square-root learning rate.

Dropout keep-masks are drawn as ``bernoulli(key, 1 - rate, (B, N, d))``
with the key of layer ``l`` at step ``s`` being
``fold_in(fold_in(fold_in(PRNGKey(train_seed), s), 0), l)`` split in two
(attention output, MLP output): the configuration's dropout stream.

Weights are made here from a seed (``init_params``), never taken from
the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e9


def mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HI,
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------
def shapes(c):
    """Canonical weight shapes of config dict ``c`` (stacked over
    layers)."""
    L, d, f, V = c["num_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    hd = c["num_heads"] * c["head_dim"]
    return {"tok": (V, d), "unembed": (d, V),
            "lnf_scale": (d,), "lnf_bias": (d,),
            "ln1_scale": (L, d), "ln1_bias": (L, d),
            "wq": (L, d, hd), "wk": (L, d, hd), "wv": (L, d, hd),
            "wo": (L, hd, d),
            "ln2_scale": (L, d), "ln2_bias": (L, d),
            "w_up": (L, d, f), "w_down": (L, f, d)}


def seed_key(seed: int):
    """Raw threefry key data of a seed of up to 64 bits."""
    import numpy as np
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def init_params(key, c, dtype=jnp.float32):
    """Weights and centroids from ``key`` (raw uint32[2]): embeddings
    N(0, 0.02^2), projections N(0, 1/fan_in), norms 1 and 0, centroids on
    the sphere of radius sqrt(dh)."""
    shp = shapes(c)
    names = sorted(shp)
    ks = dict(zip(names + ["mu"], jax.random.split(key, len(names) + 1)))
    p = {}
    for n in names:
        s = shp[n]
        if n.endswith("_scale"):
            p[n] = jnp.ones(s, dtype)
        elif n.endswith("_bias"):
            p[n] = jnp.zeros(s, dtype)
        elif n == "tok":
            p[n] = (jax.random.normal(ks[n], s) * 0.02).astype(dtype)
        else:
            p[n] = (jax.random.normal(ks[n], s)
                    / jnp.sqrt(s[-2])).astype(dtype)
    hr, kc, dh = c["routing_heads"], c["num_clusters"], c["head_dim"]
    mu = jax.random.normal(ks["mu"], (c["num_layers"], hr, kc, dh))
    mu = mu / (jnp.linalg.norm(mu, axis=-1, keepdims=True) + 1e-6)
    return p, mu * jnp.sqrt(jnp.float32(dh))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def layer_norm(x, scale=None, bias=None, eps=1e-6):
    m = x.mean(-1, keepdims=True)
    v = jnp.square(x - m).mean(-1, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps)
    if scale is not None:
        y = y * scale + bias
    return y


def rope(x, theta):
    """x (B, H, N, dh): rotate pairs (2i, 2i+1) by pos * theta^(-2i/dh)."""
    N, dh = x.shape[2], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(N, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def local_attention(q, k, v, window):
    """Causal attention of each block of ``window`` queries to its own
    block and the previous one. q, k, v: (B, H, N, dh), N % window == 0."""
    B, H, N, dh = q.shape
    nb, w = N // window, window
    qb, kb, vb = (a.reshape(B, H, nb, w, dh) for a in (q, k, v))
    prev = lambda a: jnp.concatenate([jnp.zeros_like(a[:, :, :1]),
                                      a[:, :, :-1]], 2)
    kc = jnp.concatenate([prev(kb), kb], 3)             # (B,H,nb,2w,dh)
    vc = jnp.concatenate([prev(vb), vb], 3)
    s = mm("bhnqd,bhnkd->bhnqk", qb, kc) / jnp.sqrt(jnp.float32(dh))
    qpos = jnp.arange(N).reshape(nb, w)
    kpos = jnp.concatenate([qpos - w, qpos], 1)         # (nb, 2w)
    keep = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
    p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
    return mm("bhnqk,bhnkd->bhnqd", p, vc).reshape(B, H, N, dh)


def routing_attention(q, v, mu, num_clusters):
    """Shared-QK routing heads. q, v: (B, Hr, N, dh); mu (Hr, k, dh).
    Returns the outputs (B, Hr, N, dh) and the routing vectors."""
    B, H, N, dh = q.shape
    w = N // num_clusters
    r = layer_norm(q)
    scores = mm("bhnd,hkd->bhkn", r, mu)
    _, idx = jax.lax.top_k(scores, w)                   # (B,H,k,w)
    idx = jnp.sort(idx, -1)
    take = lambda a: jnp.take_along_axis(
        a, idx.reshape(B, H, -1, 1), 2).reshape(B, H, num_clusters, w, dh)
    rg, vg = take(r), take(v)
    s = mm("bhcqd,bhckd->bhcqk", rg, rg) / jnp.sqrt(jnp.float32(dh))
    keep = idx[..., :, None] >= idx[..., None, :]
    p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
    og = mm("bhcqk,bhckd->bhcqd", p, vg)
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(H)[None, :, None]
    flat = idx.reshape(B, H, -1)
    out = jnp.zeros((B, H, N, dh), jnp.float32).at[bi, hi, flat].add(
        og.reshape(B, H, -1, dh))
    cnt = jnp.zeros((B, H, N), jnp.float32).at[bi, hi, flat].add(1.0)
    return out / jnp.maximum(cnt, 1.0)[..., None], r


def centroid_update(mu, r, decay):
    """EMA of each centroid towards the mean of its arg-max members."""
    k = mu.shape[1]
    a = jax.nn.one_hot(jnp.argmax(mm("bhnd,hkd->bhnk", r, mu), -1), k)
    sums = mm("bhnk,bhnd->hkd", a, r)
    cnts = a.sum((0, 2))
    new = decay * mu + (1 - decay) * sums / jnp.maximum(cnts, 1.0)[..., None]
    return jax.lax.stop_gradient(jnp.where((cnts > 0)[..., None], new, mu))


def dropout(x, key, rate):
    if key is None or rate <= 0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def forward(p, mu, tokens, c, drop_key=None):
    """Logits (B, N, V) and the updated centroids. ``drop_key`` is the
    step's dropout key, or None for no dropout."""
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


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def decode_routing(r, v, mu, prompt_len, cap):
    """Routing heads of cached decode, for every position from
    ``prompt_len`` on: position ``t`` takes its arg-max centroid and
    attends itself and the most recent ``cap`` earlier tokens (prompt and
    decoded alike) whose arg-max centroid is the same. r, v: (H, T, dh).
    Rows before ``prompt_len`` are returned as zeros."""
    H, T, dh = r.shape
    a = jnp.argmax(mm("htd,hkd->htk", r, mu), -1)             # (H, T)
    onehot = jax.nn.one_hot(a, mu.shape[1], dtype=jnp.int32)  # (H, T, k)
    before = jnp.cumsum(onehot, 1) - onehot                   # tokens < t
    # for a key j of t's cluster c: members strictly between j and t
    c_t = jnp.take_along_axis(before, a[..., None], 2)[..., 0]   # (H, T)
    after_j = jnp.take_along_axis(
        before + onehot, a[..., None], 2)[..., 0]                # <= j
    between = c_t[:, :, None] - after_j[:, None, :]           # (H, t, j)
    t_idx = jnp.arange(T)
    same = a[:, :, None] == a[:, None, :]
    earlier = t_idx[None, :, None] > t_idx[None, None, :]
    keep = (same & earlier & (between < cap)) | (t_idx[:, None] == t_idx)
    s = mm("htd,hjd->htj", r, r) / jnp.sqrt(jnp.float32(dh))
    p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
    o = mm("htj,hjd->htd", p, v)
    return jnp.where((t_idx >= prompt_len)[None, :, None], o, 0.0)


def serve_forward(p, mu, tokens, c, *, prompt_len, cap, rounding=None):
    """Logits (T, V) of one request as the server computes them: the
    prompt (``tokens[:prompt_len]``) in one forward pass with balanced
    routing over the prompt, then each later token through the cache:
    local heads as in the forward pass (the cache holds the last two
    blocks), routing heads by ``decode_routing``. ``tokens`` may be
    padded past the served length; padding only follows what is read.
    ``rounding`` (a dtype such as float8_e4m3fn) rounds every weight and
    every matmul operand to it: the control's lower precision."""
    H, dh, hr = c["num_heads"], c["head_dim"], c["routing_heads"]
    hl = H - hr
    T = tokens.shape[0]
    P = prompt_len
    rd = lambda x: _round(x, rounding)
    p = jax.tree.map(lambda a: rd(a.astype(jnp.float32)), p)
    x = p["tok"][tokens]
    layers = {n: p[n] for n in ("ln1_scale", "ln1_bias", "wq", "wk", "wv",
                                "wo", "ln2_scale", "ln2_bias", "w_up",
                                "w_down")}

    def heads(a):
        return a.reshape(1, T, H, dh).transpose(0, 2, 1, 3)

    def layer(x, xs):
        w, mu_l = xs
        h = layer_norm(x, w["ln1_scale"], w["ln1_bias"])
        q, k, v = (heads(mm("td,de->te", rd(h), w[n]))
                   for n in ("wq", "wk", "wv"))
        o_l = local_attention(rope(q[:, :hl], c["rope_theta"]),
                              rope(k[:, :hl], c["rope_theta"]), v[:, :hl],
                              c["local_window"])[0]
        o_pre, _ = routing_attention(q[:, hl:, :P], v[:, hl:, :P], mu_l,
                                     c["num_clusters"])
        r = layer_norm(q[0, hl:])
        o_dec = decode_routing(r, v[0, hl:], mu_l, P, cap)
        o_r = o_dec.at[:, :P].set(o_pre[0])
        o = jnp.concatenate([o_l, o_r], 0).transpose(1, 0, 2)
        x = x + mm("te,ed->td", rd(o.reshape(T, H * dh)), w["wo"])
        h2 = layer_norm(x, w["ln2_scale"], w["ln2_bias"])
        f = jax.nn.relu(mm("td,df->tf", rd(h2), w["w_up"]))
        return x + mm("tf,fd->td", rd(f), w["w_down"]), None

    x, _ = jax.lax.scan(layer, x, (layers, mu))
    x = layer_norm(x, p["lnf_scale"], p["lnf_bias"])
    return mm("td,dv->tv", rd(x), p["unembed"])


@functools.partial(jax.jit, static_argnames=("c", "prompt_len", "cap",
                                             "rounding"))
def served_token_gaps(p, mu, tokens, served, *, c, prompt_len, cap,
                      rounding=None):
    """For each served token ``served[i]`` (produced from position
    ``prompt_len - 1 + i``; -1 marks padding): how far the reference's
    logit of it lies below the reference's best there. With
    ``rounding``, the served tokens are instead the ones the reference
    at that precision puts first (the control)."""
    c = dict(c)
    ref = serve_forward(p, mu, tokens, c, prompt_len=prompt_len, cap=cap)
    n = served.shape[0]
    rows = jax.lax.dynamic_slice_in_dim(ref, prompt_len - 1, n, 0)
    if rounding is not None:
        low = serve_forward(p, mu, tokens, c, prompt_len=prompt_len,
                            cap=cap, rounding=rounding)
        served = jnp.where(served >= 0, jnp.argmax(
            jax.lax.dynamic_slice_in_dim(low, prompt_len - 1, n, 0), -1), -1)
    got = jnp.take_along_axis(rows, jnp.maximum(served, 0)[:, None], 1)[:, 0]
    return jnp.where(served >= 0, rows.max(-1) - got, 0.0)


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------
def learning_rate(t, t_cfg):
    """Linear warm-up then inverse square root, at step ``t`` >= 1."""
    w = float(t_cfg["warmup_steps"])
    return (t_cfg["lr"] * min(1.0, t / w) * (w / max(t, w)) ** 0.5)


@functools.partial(jax.jit, static_argnames=("c", "t"))
def _train_step(p, mu, opt, batch, drop_key, lr, *, c, t):
    """One step: loss, clipped gradient, Adam. ``c`` and ``t`` are the
    model and training settings as hashable tuples of items."""
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
    return p, new_mu, {"m": m, "v": v, "count": n}, loss, g


@functools.partial(jax.jit, static_argnames=("c",))
def make_params(key, *, c):
    """``init_params`` in one jitted call; ``c`` as ``frozen(config)``."""
    return init_params(key, dict(c))


@jax.jit
def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


@jax.jit
def leaf_gap_norms(a, b):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a[n].astype(jnp.float32)
                                           - b[n].astype(jnp.float32))))
            for n in a}


def train(key, batches, c, t, train_seed: int):
    """Run ``len(batches)`` steps from the weights of ``key``. Returns
    the losses, the per-leaf norms of step 1's clipped gradient, and the
    per-leaf norms of the weights' change over all the steps."""
    p, mu = make_params(key, c=frozen(c))
    p0 = p
    opt = {"m": jax.tree.map(jnp.zeros_like, p),
           "v": jax.tree.map(jnp.zeros_like, p),
           "count": jnp.zeros((), jnp.int32)}
    root = jax.random.PRNGKey(train_seed)
    losses, g1 = [], None
    for s, batch in enumerate(batches):
        drop = jax.random.fold_in(root, s) if c["dropout"] > 0 else None
        p, mu, opt, loss, g = _train_step(
            p, mu, opt, batch, drop, learning_rate(s + 1, t),
            c=frozen(c), t=frozen(t))
        losses.append(float(loss))
        if g1 is None:
            g1 = jax.device_get(leaf_norms(g))
        del g
    dp = jax.device_get(leaf_gap_norms(p, p0))
    return losses, g1, dp


def frozen(d):
    """A config dict as a hashable, sorted tuple of items."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))
