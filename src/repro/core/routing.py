"""Routing attention — Algorithm 1 of Roy et al. 2020, batched + multi-head.

Pipeline (per head):
  1. routing vectors r = LN_no-scale-bias(q) (and r_k for the non-shared
     case);   shared-QK in the causal/LM setting (paper Section 4.1).
  2. affinities  S = r @ mu^T                      (B, H, N, k)
  3. balanced membership: per-centroid top-w over tokens, indices sorted
     ascending to preserve temporal order          (B, H, k, w)
  4. gather q/k/v rows, intra-cluster attention with a causal mask on
     *original positions*, fp32 softmax            (B, H, k, w, w)
  5. scatter back to sequence order (scatter-mean over duplicate
     memberships; tokens selected by no cluster output 0)
  6. EMA centroid update (k-means state is returned, not mutated).

Complexity: O(nkd) for step 2 + O(k w^2 d) = O(n^2 d / k) for step 4;
k = sqrt(n) gives the paper's O(n^1.5 d).

The O(k w^2 d) attention (step 4) is the compute hot-spot. `impl="xla"`
(the default, and the reference) gathers (B,H,k,w,dh) blocks and attends
them in XLA; `impl="pallas_fused"` runs the Pallas TPU kernel
(`repro.kernels.routing_attention`): q/k/v stay in sequence layout, the
membership indices ride in as per-cluster SMEM blocks, and step 4's
gathers never touch HBM (DESIGN.md §9). The kernel chooses its own
memory plan from N·dh and is differentiable (a custom flash-style VJP).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RoutingConfig, with_overrides
from repro.core.kmeans import (KMeansState, cluster_scores, ema_update,
                               normalize_routing)
from repro.obs.trace import span

_BIG_NEG = -1e9
_IMPLS = ("xla", "pallas_fused")


class RoutingOutput(NamedTuple):
    out: jax.Array                      # (B, H, N, dh)
    state: KMeansState                  # updated centroids
    attn: Optional[jax.Array] = None    # (B,H,k,w,w) if return_attn
    q_idx: Optional[jax.Array] = None   # (B,H,k,w) if return_attn
    stats: Optional[Any] = None         # obs.RoutingStats if cfg.stats


def balanced_topk(scores: jax.Array, window: int,
                  valid: Optional[jax.Array] = None) -> jax.Array:
    """Per-centroid balanced top-w membership (Algorithm 1 lines 12-18).

    scores: (B, H, N, k) centroid affinities.
    valid:  (B, N) bool; padding is pushed to -inf so it is only selected
            once every real token is taken.
    Returns sorted indices (B, H, k, w).
    """
    if valid is not None:
        scores = jnp.where(valid[:, None, :, None], scores, _BIG_NEG)
    per_centroid = jnp.swapaxes(scores, -1, -2)          # (B,H,k,N)
    _, idx = jax.lax.top_k(per_centroid, window)         # (B,H,k,w)
    return jnp.sort(idx, axis=-1)                        # preserve order


def _gather_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x: (B,H,N,d), idx: (B,H,k,w) -> (B,H,k,w,d)."""
    B, H, N, d = x.shape
    _, _, k, w = idx.shape
    flat = jnp.take_along_axis(x, idx.reshape(B, H, k * w, 1), axis=2)
    return flat.reshape(B, H, k, w, d)


def _scatter_rows(og: jax.Array, idx: jax.Array, n: int,
                  mode: str) -> jax.Array:
    """Scatter per-cluster outputs back to the sequence.

    og: (B,H,k,w,d), idx: (B,H,k,w) -> (B,H,n,d).
    mode="mean": scatter-add + divide by membership count (default).
    mode="last": plain scatter, later clusters win (Alg. 1 line 27 verbatim).
    """
    B, H, k, w, d = og.shape
    flat_og = og.reshape(B, H, k * w, d)
    flat_idx = idx.reshape(B, H, k * w)
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(H)[None, :, None]
    if mode == "last":
        out = jnp.zeros((B, H, n, d), og.dtype)
        return out.at[bi, hi, flat_idx].set(flat_og)
    out = jnp.zeros((B, H, n, d), jnp.float32)
    out = out.at[bi, hi, flat_idx].add(flat_og.astype(jnp.float32))
    cnt = jnp.zeros((B, H, n), jnp.float32)
    cnt = cnt.at[bi, hi, flat_idx].add(1.0)
    return (out / jnp.maximum(cnt, 1.0)[..., None]).astype(og.dtype)


def routed_attention(q: jax.Array,
                     k: Optional[jax.Array],
                     v: jax.Array,
                     state: KMeansState,
                     cfg: RoutingConfig,
                     positions: Optional[jax.Array] = None,
                     pad_mask: Optional[jax.Array] = None,
                     update_state: bool = True,
                     return_attn: bool = False,
                     impl: str = "xla",
                     interpret: Optional[bool] = None) -> RoutingOutput:
    """Content-routed sparse attention.

    q, v: (B, H, N, dh); k: same or None (shared-QK causal mode).
    positions: (B, N) int32 original positions (defaults to arange) — the
        causal mask is evaluated on these, which is what makes gathered
        blocks order-correct.
    pad_mask: (B, N) bool, True = real token. Padding is excluded from
        top-k selection, attention, and centroid updates (paper Section 4.1).
    impl: "xla" reference | "pallas_fused" gather-free kernel
        (sequence-layout q/k/v, SMEM membership blocks — no (B,H,k,w,dh)
        q/k/v intermediates in HBM; the kernel's memory plan switches to
        double-buffered row DMA from HBM past its residency budget).
    interpret: Pallas interpret mode for the kernel impl; None derives
        from the platform (compiled on TPU, interpret elsewhere).
    """
    if impl not in _IMPLS:
        raise ValueError(f"routed_attention: impl {impl!r} is not one of "
                         f"{_IMPLS}")
    B, H, N, dh = q.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))

    # --- segmented (shard-local) routing: fold sequence chunks into the
    # batch so assignment/top-k/gather never cross segment boundaries.
    # Causality is preserved (the mask uses original positions and every
    # segment only holds a contiguous span). Centroids are shared across
    # segments; with segments == TP width the fold aligns with the
    # model-axis seq sharding and routing becomes collective-free.
    ns = cfg.segments
    if ns > 1 and N % ns == 0 and N // ns >= cfg.num_clusters:
        Nl = N // ns

        def fold(x):          # (B,H,N,d) -> (B*ns,H,Nl,d)
            return x.reshape(B, H, ns, Nl, -1).transpose(0, 2, 1, 3, 4) \
                    .reshape(B * ns, H, Nl, -1)

        def fold2(x):         # (B,N) -> (B*ns,Nl)
            return x.reshape(B * ns, Nl)

        sub = with_overrides(cfg, segments=1)
        out = routed_attention(
            fold(q), None if k is None else fold(k), fold(v), state, sub,
            positions=fold2(positions),
            pad_mask=None if pad_mask is None else fold2(pad_mask),
            update_state=update_state, return_attn=False, impl=impl,
            interpret=interpret)
        o = out.out.reshape(B, ns, H, Nl, dh).transpose(0, 2, 1, 3, 4) \
                   .reshape(B, H, N, dh)
        # stats were computed on the folded (B*ns) batch: per-head means
        # over segments, which is exactly the shard-local health signal
        return RoutingOutput(out=o, state=out.state, stats=out.stats)

    w = min(cfg.window or max(1, N // cfg.num_clusters), N)
    shared = cfg.share_qk and cfg.causal

    # spans (repro.obs) name the stages in the compiled step's op_name
    # metadata, so the device trace attributes their time
    with span("routing/assign"):
        r_q = normalize_routing(q)
        if shared:
            r_k, k_attn = r_q, r_q
        else:
            r_k = normalize_routing(k if k is not None else q)
            k_attn = r_k

        scores_q = cluster_scores(r_q, state.mu)         # (B,H,N,k)
        q_idx = balanced_topk(scores_q, w, pad_mask)     # (B,H,k,w)
        if shared:
            k_idx = q_idx
        else:
            scores_k = cluster_scores(r_k, state.mu)
            k_idx = balanced_topk(scores_k, w, pad_mask)

    if impl == "pallas_fused":
        # gather-free: q/k/v stay in sequence layout; the kernel pulls
        # member rows through per-cluster SMEM index blocks and the mask
        # compares pre-gathered member positions. Shared QK passes the
        # one index array (keys are the q rows).
        from repro.kernels import ops as kops
        og = kops.routed_attention_fused(
            r_q, None if shared else k_attn, v, q_idx,
            None if shared else k_idx,
            positions.astype(jnp.int32), causal=cfg.causal,
            kvalid=pad_mask, interpret=interpret)
        attn = None
    else:
        with span("routing/gather"):
            qg = _gather_rows(r_q, q_idx)                # (B,H,k,w,dh)
            # shared-QK causal: k_attn is r_q and k_idx is q_idx, so the
            # key gather is identical to the query gather — reuse it
            kg = qg if shared else _gather_rows(k_attn, k_idx)
            vg = _gather_rows(v, k_idx)
            pos = positions[:, None, :].astype(jnp.int32)
            pos_q = jnp.take_along_axis(
                jnp.broadcast_to(pos, (B, H, N)), q_idx.reshape(B, H, -1),
                axis=2).reshape(B, H, q_idx.shape[2], w)
            pos_k = pos_q if shared else jnp.take_along_axis(
                jnp.broadcast_to(pos, (B, H, N)), k_idx.reshape(B, H, -1),
                axis=2).reshape(B, H, k_idx.shape[2], w)

            valid_k = None
            if pad_mask is not None:
                vm = jnp.broadcast_to(pad_mask[:, None, :], (B, H, N))
                valid_k = jnp.take_along_axis(
                    vm, k_idx.reshape(B, H, -1), axis=2).reshape(pos_k.shape)

        with span("routing/attend"):
            og, attn = _block_attention(qg, kg, vg, pos_q, pos_k,
                                        cfg.causal, valid_k, return_attn)

    with span("routing/scatter"):
        out = _scatter_rows(og, q_idx, N, cfg.scatter_mode)
    new_state = state
    if update_state:
        with span("routing/kmeans_update"):
            new_state = ema_update(
                state, r_q, None if shared else r_k, pad_mask, cfg.decay)
    stats = None
    if cfg.stats:
        # routing-health telemetry (repro.obs, DESIGN.md §10): reuses the
        # scores/membership computed above; the static `if` keeps the
        # stats-off HLO byte-identical to a build without the flag
        from repro.kernels.routing_attention import computed_tile_share
        from repro.obs.routing_stats import compute_routing_stats
        stats = compute_routing_stats(
            r_q, k_attn, state.mu, new_state.mu, scores_q, q_idx, k_idx,
            positions, pad_mask, cfg.causal,
            computed_tile_share(positions, q_idx, k_idx, cfg.causal,
                                kvalid=pad_mask),
            probes=cfg.stats_probes)
    return RoutingOutput(out=out, state=new_state,
                         attn=attn if return_attn else None,
                         q_idx=q_idx if return_attn else None,
                         stats=stats)


def _block_attention(qg, kg, vg, pos_q, pos_k, causal, valid_k, return_attn):
    """Intra-cluster attention on gathered blocks (pure-JAX reference)."""
    dh = qg.shape[-1]
    logits = jnp.einsum("bhkwd,bhkud->bhkwu", qg, kg).astype(jnp.float32)
    logits = logits / jnp.sqrt(dh).astype(jnp.float32)
    keep = jnp.ones(logits.shape, bool)
    if causal:
        keep &= pos_q[..., :, None] >= pos_k[..., None, :]
    if valid_k is not None:
        keep &= valid_k[..., None, :]
    logits = jnp.where(keep, logits, _BIG_NEG)
    attn = jax.nn.softmax(logits, axis=-1)
    # queries whose cluster holds no attendable key (separate-QK causal
    # case: all keys in the future) output 0, not a uniform average
    attn = jnp.where(keep.any(-1, keepdims=True), attn, 0.0)
    og = jnp.einsum("bhkwu,bhkud->bhkwd", attn.astype(vg.dtype), vg)
    return og, (attn if return_attn else None)


def routing_attention_dense_oracle(q, k, v, state, cfg, positions=None,
                                   pad_mask=None):
    """O(n^2) oracle: dense attention masked to same-cluster pairs.

    Used by tests: builds the (n x n) mask implied by the balanced top-k
    membership and checks `routed_attention` against dense masked softmax.
    Only supports scatter_mode="mean".
    """
    B, H, N, dh = q.shape
    w = min(cfg.window or max(1, N // cfg.num_clusters), N)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    r_q = normalize_routing(q)
    if cfg.share_qk and cfg.causal:
        r_k, k_attn = r_q, r_q
    else:
        r_k = normalize_routing(k if k is not None else q)
        k_attn = r_k
    scores_q = cluster_scores(r_q, state.mu)
    q_idx = balanced_topk(scores_q, w, pad_mask)
    if cfg.share_qk and cfg.causal:
        k_idx = q_idx
    else:
        k_idx = balanced_topk(cluster_scores(r_k, state.mu), w, pad_mask)

    # membership[b,h,c,n] = token n belongs to cluster c (as query / as key)
    memb_q = jax.nn.one_hot(q_idx, N, dtype=jnp.float32).sum(3) > 0
    memb_k = jax.nn.one_hot(k_idx, N, dtype=jnp.float32).sum(3) > 0
    out = jnp.zeros((B, H, N, dh), jnp.float32)
    cnt = jnp.zeros((B, H, N), jnp.float32)
    nclusters = q_idx.shape[2]
    for c in range(nclusters):   # oracle: loop is fine for test sizes
        pair = memb_q[:, :, c, :, None] & memb_k[:, :, c, None, :]
        logits = jnp.einsum("bhnd,bhmd->bhnm", r_q, k_attn) / jnp.sqrt(dh)
        keep = pair
        if cfg.causal:
            keep &= (positions[:, None, :, None]
                     >= positions[:, None, None, :])
        if pad_mask is not None:
            keep &= pad_mask[:, None, None, :]
        logits = jnp.where(keep, logits.astype(jnp.float32), _BIG_NEG)
        attn = jax.nn.softmax(logits, axis=-1)
        attn = jnp.where(keep.any(-1, keepdims=True), attn, 0.0)
        o_c = jnp.einsum("bhnm,bhmd->bhnd", attn, v.astype(jnp.float32))
        sel = memb_q[:, :, c, :]
        out = out + jnp.where(sel[..., None], o_c, 0.0)
        cnt = cnt + sel.astype(jnp.float32)
    out = out / jnp.maximum(cnt, 1.0)[..., None]
    return out.astype(q.dtype)
