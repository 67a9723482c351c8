"""Routing-health statistics for content-based sparse attention.

The paper's complexity bound and quality claims both assume the online
k-means stays healthy: balanced occupancy (collapse breaks the O(n^1.5)
cost), live centroids, and a routed pattern that actually captures the
attention mass a dense model would spend. This module computes those
signals *inside* the jitted step, from intermediates the routing layer
already has (scores, balanced membership) — stats-on cost is dominated by
one (P, N) probe softmax with P = ``stats_probes`` rows.

Per routing layer (leaves shaped over that layer's routing heads H):

  occupancy  (H, k)  batch-mean token count per centroid (argmax
                     assignment, padding excluded)
  entropy    (H,)    occupancy entropy in nats; log(k) = perfectly
                     balanced, 0 = collapsed
  dead       (H,)    centroids with zero assigned tokens (batch mean)
  drift      (H,)    mean_k ||mu_t - mu_{t-1}||_2 — centroid movement of
                     this step's EMA update (0 when update_state=False)
  mismatch   (H,)    fraction of tokens whose argmax centroid did NOT
                     select them under balanced top-w membership — how
                     much the load-balancing constraint distorts the
                     nearest-centroid assignment
  recall     (H,)    sampled attention recall: on P strided probe
                     queries, the fraction of full-softmax attention
                     mass (same normalized q/k, same causal/pad masks)
                     that falls on keys the routed pattern can reach
  live_tiles (H,)    share of the fused routing kernels' (bq, bk)
                     sub-tile pairs computed for this membership (batch
                     mean): T(T+1)/2 of T² where a cluster's members
                     keep to the causal band, 1 where they do not or
                     attention is not causal (computed by core.routing)

Everything is fp32 and stop_gradient'ed: stats must never change grads.
This module imports jax + stdlib only (obs stays below repro.core in the
import DAG); ``core.routing`` passes its intermediates in.

Host-side helpers at the bottom (``summarize`` / ``flatten`` /
``pages_health``) fold stats trees into scalar metric dicts and read
cluster-page occupancy straight off a serving cache's ``rlen`` leaves.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

_BIG_NEG = -1e9
_EPS = 1e-12

SCALAR_FIELDS = ("entropy", "dead", "drift", "mismatch", "recall",
                 "live_tiles")


class RoutingStats(NamedTuple):
    occupancy: jax.Array    # (H, k)
    entropy: jax.Array      # (H,)
    dead: jax.Array         # (H,)
    drift: jax.Array        # (H,)
    mismatch: jax.Array     # (H,)
    recall: jax.Array       # (H,)
    live_tiles: jax.Array   # (H,)


def _probe_idx(n: int, probes: int):
    """Static strided probe positions: the last token of each of P
    equal chunks (later tokens have non-trivial causal history)."""
    p = max(1, min(int(probes), n))
    stride = n // p
    return tuple(int((i + 1) * stride - 1) for i in range(p))


def compute_routing_stats(r_q: jax.Array, k_attn: jax.Array,
                          mu_prev: jax.Array, mu_new: jax.Array,
                          scores_q: jax.Array, q_idx: jax.Array,
                          k_idx: jax.Array, positions: jax.Array,
                          pad_mask: Optional[jax.Array], causal: bool,
                          live_tiles: jax.Array,
                          probes: int = 8) -> RoutingStats:
    """All inputs are the routing layer's own intermediates:

    r_q/k_attn (B,H,N,dh) normalized routing vectors / attention keys,
    mu_prev/mu_new (H,k,dh) centroids before/after the EMA update,
    scores_q (B,H,N,k) centroid affinities, q_idx/k_idx (B,H,k,w)
    balanced memberships, positions (B,N), pad_mask (B,N) or None,
    live_tiles (H,) the fused kernels' computed sub-tile share.
    """
    B, H, N, dh = r_q.shape
    kc = scores_q.shape[-1]
    f32 = jnp.float32
    valid = (jnp.ones((B, N), f32) if pad_mask is None
             else pad_mask.astype(f32))                    # (B,N)

    # --- occupancy / entropy / dead (argmax assignment, pad excluded)
    assign = jnp.argmax(scores_q, axis=-1)                 # (B,H,N)
    onehot = jax.nn.one_hot(assign, kc, dtype=f32)         # (B,H,N,k)
    onehot = onehot * valid[:, None, :, None]
    counts = jnp.einsum("bhnk->bhk", onehot)               # (B,H,k)
    total = jnp.maximum(counts.sum(-1, keepdims=True), 1.0)
    p = counts / total
    entropy = -(p * jnp.log(jnp.maximum(p, _EPS))).sum(-1)  # (B,H)
    dead = (counts <= 0.0).astype(f32).sum(-1)              # (B,H)

    # --- centroid drift of this step's EMA update
    drift = jnp.linalg.norm(
        mu_new.astype(f32) - mu_prev.astype(f32), axis=-1).mean(-1)  # (H,)

    # --- balanced-vs-nearest mismatch
    # memb_q[b,h,c,n]: token n selected by cluster c under balanced top-w
    memb_q = jax.nn.one_hot(q_idx, N, dtype=f32).sum(3)    # (B,H,k,N)
    memb_q = (memb_q > 0).astype(f32)
    taken = jnp.take_along_axis(
        memb_q, assign[:, :, None, :], axis=2)[:, :, 0, :]  # (B,H,N)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    mismatch = 1.0 - (taken * valid[:, None, :]).sum((0, 2)) / n_valid

    # --- sampled attention recall on strided probe queries
    pidx = jnp.asarray(_probe_idx(N, probes), jnp.int32)   # (P,) static
    rq_p = jnp.take(r_q, pidx, axis=2).astype(f32)         # (B,H,P,dh)
    logits = jnp.einsum("bhpd,bhnd->bhpn", rq_p,
                        k_attn.astype(f32)) / jnp.sqrt(float(dh))
    keep = jnp.ones(logits.shape, bool)
    if causal:
        pos_p = jnp.take(positions, pidx, axis=1)          # (B,P)
        keep &= (pos_p[:, None, :, None]
                 >= positions[:, None, None, :])
    keep &= valid[:, None, None, :] > 0
    attn = jax.nn.softmax(jnp.where(keep, logits, _BIG_NEG), axis=-1)
    attn = jnp.where(keep.any(-1, keepdims=True), attn, 0.0)
    memb_k = jax.nn.one_hot(k_idx, N, dtype=f32).sum(3)    # (B,H,k,N)
    memb_k = (memb_k > 0).astype(f32)
    memb_q_p = jnp.take(memb_q, pidx, axis=3)              # (B,H,k,P)
    pattern = jnp.einsum("bhcp,bhcn->bhpn", memb_q_p, memb_k) > 0
    captured = (attn * pattern).sum(-1)                    # (B,H,P)
    pv = jnp.take(valid, pidx, axis=1)                     # (B,P)
    recall = ((captured * pv[:, None, :]).sum((0, 2))
              / jnp.maximum(pv.sum(), 1.0))                # (H,)

    return jax.tree.map(jax.lax.stop_gradient, RoutingStats(
        occupancy=counts.mean(0),
        entropy=entropy.mean(0),
        dead=dead.mean(0),
        drift=drift,
        mismatch=mismatch,
        recall=recall,
        live_tiles=live_tiles.astype(f32)))


# ---------------------------------------------------------------------------
# Tree folding (train-step metrics / engine records)
# ---------------------------------------------------------------------------
def stats_leaves(tree) -> list:
    """Every RoutingStats instance anywhere in ``tree``."""
    return [leaf for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, RoutingStats))
        if isinstance(leaf, RoutingStats)]


def summarize(tree) -> Dict[str, jax.Array]:
    """Model-wide scalar means over every RoutingStats in ``tree``:
    {"routing/entropy": ..., "routing/dead": ..., ...}. Empty dict when
    the tree holds no stats."""
    leaves = stats_leaves(tree)
    if not leaves:
        return {}
    out = {}
    for f in SCALAR_FIELDS:
        vals = jnp.concatenate(
            [getattr(s, f).astype(jnp.float32).ravel() for s in leaves])
        out[f"routing/{f}"] = vals.mean()
    return out


def flatten(seg_stats, prefix: str = "rt") -> Dict[str, jax.Array]:
    """Per-layer detail from the stack's stats structure (a list over
    segments of {layer_index_str: RoutingStats}, leaves stacked over the
    segment's scan groups): "rt/{seg}/{layer}/{field}" -> array."""
    out: Dict[str, jax.Array] = {}
    for si, seg in enumerate(seg_stats):
        for li in sorted(seg):
            st = seg[li]
            for f in SCALAR_FIELDS:
                out[f"{prefix}/{si}/{li}/{f}"] = getattr(st, f)
    return out


# ---------------------------------------------------------------------------
# Serving-side pages health (host, numpy — no trace)
# ---------------------------------------------------------------------------
def pages_health(cache, active=None) -> Optional[Dict[str, Any]]:
    """Cluster-page occupancy health straight off a serving cache.

    Walks ``cache`` (the engine pool or a single lane, host values) for
    ``rlen`` leaves — (G, B, Hr, kc) per-page token counts of the
    cluster-paged routing cache — and returns batch-mean occupancy
    entropy (nats) and dead-page count over ``active`` slots. None when
    the stack has no routing pages or no slot is active.
    """
    import numpy as np
    rlens = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = ""
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = entry.key
                break
        if name == "rlen":
            rlens.append(np.asarray(leaf))
    if not rlens:
        return None
    ents, deads = [], []
    for rl in rlens:                       # (G,B,Hr,kc)
        rl = rl.astype(np.float64)
        if active is not None:
            rl = rl[:, np.asarray(active, bool)]
        if rl.size == 0 or rl.shape[1] == 0:
            continue
        tot = rl.sum(-1)                   # (G,B,Hr)
        occupied = tot > 0
        if not occupied.any():
            continue
        p = rl / np.maximum(tot, 1.0)[..., None]
        ent = -(p * np.log(np.maximum(p, _EPS))).sum(-1)
        ents.append(ent[occupied])
        deads.append((rl <= 0).sum(-1)[occupied])
    if not ents:
        return None
    return {"routing/entropy": float(np.concatenate(ents).mean()),
            "routing/dead": float(np.concatenate(deads).mean())}
