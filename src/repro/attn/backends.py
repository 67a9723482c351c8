"""The built-in attention backends: XLA references + Pallas kernels.

Registered pairs (variant, impl):

  full/xla           dense or online-softmax chunked reference
                     (core.attention), append-cache decode
  full/pallas        flash-attention kernel (kernels.flash_attention)
  local/xla          blocked sliding-window reference (core.local),
                     ring-cache decode
  local/pallas       blocked local kernel (kernels.local_attention)
  routing/xla        Algorithm-1 reference (core.routing),
                     cluster-paged decode
  routing/pallas_fused   gather-free fused kernel: sequence-layout q/k/v,
                     membership via per-cluster SMEM blocks — no
                     (B,H,k,w,dh) q/k/v intermediates in HBM (DESIGN.md
                     §9). The kernel chooses its memory plan from N·dh
                     (whole planes resident in VMEM, or member rows
                     DMA'd from HBM), so it serves every sequence length
  routing/pallas_paged   fused apply + the paged-decode kernel
                     (kernels.routing_decode): single-token decode DMAs
                     only the selected cluster page into VMEM via
                     scalar-prefetched page tables — decode is gather-
                     free too, and resolves here on TPU (priority 20)
  local+routing/xla      paper head split, both halves reference
  local+routing/pallas_fused  both halves Pallas: window kernel + fused
                     routing
  local+routing/pallas_paged  fused apply; decode = ring-local reference
                     + paged routing kernel

Every Pallas backend is differentiable (the kernels carry flash-style
custom VJPs), so each is legal on the train path. Decode: the routing
variants resolve to ``pallas_paged`` on TPU — token- and
cache-trajectory bit-parity with the xla cluster-paged reference (the
kernel shares the reference's routing + cache-write code and mirrors its
attention op sequence; per-step outputs agree to float ulps, see
kernels.routing_decode); full/local decode stays on the xla append/ring
references (already gather-free).

Rope is applied *here*, per variant: full/local heads are roped, routing
heads are not (their routing vectors and shared-QK attention keys are
content, and the paper's causal mask runs on original positions), and
the local+routing split ropes only its local half. Callers hand in raw
(un-roped) q/k/v plus positions.

Every backend with a decode path also owns its cache layout as a typed
``CacheLayout`` object: how the leaf dict is built (``init``), how
prefill fills it (``fill``), which leaf axes carry heads (sharding
hints), per-leaf reset fill values, and which leaves are cluster-paged
(``pageable_leaves`` + ``page_len_leaf``, consumed by the tiered KV
store for per-page compaction). The slot-pooled serving engine and the
KV store consume all of it through the registry.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.attn import registry
from repro.attn.registry import Backend, CacheLayout, Capabilities
from repro.attn.spec import AttentionSpec, head_split, resolve_chunk
from repro.core.attention import full_attention
from repro.core.kmeans import KMeansState, normalize_routing
from repro.core.local import local_attention
from repro.core.routing import routed_attention
from repro.models import layers as L
from repro.obs.trace import span

_BIG_NEG = -1e9


# ---------------------------------------------------------------------------
# Shared glue
# ---------------------------------------------------------------------------
def _rope_qk(spec: AttentionSpec, q, k, positions):
    """Rope q (and k when given) at ``positions`` (default arange)."""
    if spec.rope_theta is None:
        return q, k
    B, _, N, _ = q.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    with span("model/attention_proj"):
        q = L.apply_rope(q, positions, spec.rope_theta)
        if k is not None:
            k = L.apply_rope(k, positions, spec.rope_theta)
    return q, k


def _expand_kv(x: jax.Array, reps: int) -> jax.Array:
    return jnp.repeat(x, reps, axis=1) if reps > 1 else x


def _split_heads(spec: AttentionSpec, q, k, v):
    """Slice q/k/v into the (local, routing) halves of a local+routing
    split, mirroring the paper's layout: local heads first."""
    Hl, Hr, kvl, kvr = head_split(spec)
    if spec.num_kv_heads == 1:
        kl = kr = k
        vl = vr = v
    else:
        kl, kr = (None, None) if k is None else (k[:, :kvl], k[:, kvl:])
        vl, vr = v[:, :kvl], v[:, kvl:]
    return (q[:, :Hl], kl, vl), (q[:, Hl:], kr, vr)


def _local_subspec(spec: AttentionSpec) -> AttentionSpec:
    Hl, _, kvl, _ = head_split(spec)
    return replace(spec, variant="local", num_heads=Hl, num_kv_heads=kvl,
                   routing=None, routing_heads=0)


def _routing_subspec(spec: AttentionSpec) -> AttentionSpec:
    _, Hr, _, kvr = head_split(spec)
    return replace(spec, variant="routing", num_heads=Hr, num_kv_heads=kvr,
                   window=0, routing_heads=0)


# ---------------------------------------------------------------------------
# Apply (train / prefill) paths
# ---------------------------------------------------------------------------
def _full_xla_apply(spec, q, k, v, *, state=None, positions=None,
                    pad_mask=None, update_state=True, interpret=None):
    qr, kr = _rope_qk(spec, q, k, positions)
    o = full_attention(qr, kr, v, spec.causal, pad_mask,
                       positions=positions,
                       chunk=resolve_chunk(spec, q.shape[2]),
                       logit_scale=spec.logit_scale)
    return o, state


def _block_size(n: int, pref: int = 128) -> int:
    """Largest kernel block <= pref that divides n (fall back to n)."""
    for b in (pref, pref // 2, pref // 4):
        if b and n % b == 0:
            return b
    return n


def _full_pallas_apply(spec, q, k, v, *, state=None, positions=None,
                       pad_mask=None, update_state=True, interpret=None):
    from repro.kernels import ops as kops
    qr, kr = _rope_qk(spec, q, k, positions)
    o = kops.flash_attention(qr, kr, v, causal=spec.causal,
                             bq=_block_size(q.shape[2]),
                             bk=_block_size(k.shape[2]),
                             interpret=interpret)
    return o, state


def _local_xla_apply(spec, q, k, v, *, state=None, positions=None,
                     pad_mask=None, update_state=True, interpret=None):
    qr, kr = _rope_qk(spec, q, k, positions)
    o = local_attention(qr, kr, v, spec.window, spec.causal, pad_mask)
    return o, state


def _local_pallas_apply(spec, q, k, v, *, state=None, positions=None,
                        pad_mask=None, update_state=True, interpret=None):
    from repro.kernels import ops as kops
    qr, kr = _rope_qk(spec, q, k, positions)
    o = kops.local_attention(qr, kr, v, window=min(spec.window, q.shape[2]),
                             causal=spec.causal, interpret=interpret)
    return o, state


def _make_routing_apply(kernel_impl: str):
    def apply(spec, q, k, v, *, state=None, positions=None, pad_mask=None,
              update_state=True, interpret=None):
        rc = spec.routing
        g = spec.q_per_kv
        v_e = _expand_kv(v, g)
        k_in = (None if (rc.share_qk and spec.causal) or k is None
                else _expand_kv(k, g))
        ro = routed_attention(q, k_in, v_e, KMeansState(mu=state), rc,
                              positions, pad_mask, update_state,
                              impl=kernel_impl, interpret=interpret)
        # 3-tuple: routing backends also surface the RoutingStats aux
        # (None unless rc.stats); attend() tolerates 2- and 3-tuples
        return ro.out, ro.state.mu, ro.stats
    return apply


def _make_mixed_apply(kernel_impl: str):
    """Composite apply for the local+routing head split.

    The kernel impl (``pallas_fused``) runs the local half on the Pallas
    window kernel — which carries its own flash-style custom VJP, so the
    composite gradient is kernel-backed end to end instead of mixing a
    fused routing grad with the XLA-reference local grad. The window
    kernel's affine BlockSpec pipeline already double-buffers its (w, dh)
    tiles, so its VMEM footprint is bounded by the window, never by N —
    it needs no manual paging. The reference serves the cases the kernel
    does not express (pad_mask, N not a multiple of the window)."""
    routing_apply = _make_routing_apply(kernel_impl)
    local_kernel = kernel_impl != "xla"

    def apply(spec, q, k, v, *, state=None, positions=None, pad_mask=None,
              update_state=True, interpret=None):
        (ql, kl, vl), (qr, kr, vr) = _split_heads(spec, q, k, v)
        lspec = _local_subspec(spec)
        N = q.shape[2]
        use_kernel = (local_kernel and pad_mask is None
                      and N % min(lspec.window, N) == 0)
        local_fn = _local_pallas_apply if use_kernel else _local_xla_apply
        o_l, _ = local_fn(
            lspec, ql, kl, vl, positions=positions,
            pad_mask=pad_mask, interpret=interpret)
        o_r, new_mu, stats = routing_apply(
            _routing_subspec(spec), qr, kr, vr, state=state,
            positions=positions, pad_mask=pad_mask,
            update_state=update_state, interpret=interpret)
        return jnp.concatenate([o_l, o_r], axis=1), new_mu, stats
    return apply


# ---------------------------------------------------------------------------
# Decode paths + cache layouts
# ---------------------------------------------------------------------------
def _append_cache(spec, B, max_len, dtype):
    dh, Hkv = spec.head_dim, spec.num_kv_heads
    return {"k": jnp.zeros((B, Hkv, max_len, dh), dtype),
            "v": jnp.zeros((B, Hkv, max_len, dh), dtype)}


def _ring_cache(spec, B, max_len, dtype):
    dh = spec.head_dim
    kvl = (head_split(spec)[2] if spec.variant == "local+routing"
           else spec.num_kv_heads)
    W = spec.window
    return {"lk": jnp.zeros((B, kvl, 2 * W, dh), dtype),
            "lv": jnp.zeros((B, kvl, 2 * W, dh), dtype),
            "lpos": jnp.full((B, 2 * W), -1, jnp.int32)}


def _page_dims(spec, max_len):
    kc = spec.routing.num_clusters
    cap = spec.routing.window or max(1, max_len // kc)
    return kc, cap


def _pages_cache(spec, B, max_len, dtype):
    dh = spec.head_dim
    Hr = (head_split(spec)[1] if spec.variant == "local+routing"
          else spec.num_heads)
    kc, cap = _page_dims(spec, max_len)
    return {"rk": jnp.zeros((B, Hr, kc, cap, dh), dtype),
            "rv": jnp.zeros((B, Hr, kc, cap, dh), dtype),
            "rlen": jnp.zeros((B, Hr, kc), jnp.int32)}


def _mixed_cache(spec, B, max_len, dtype):
    return {**_ring_cache(spec, B, max_len, dtype),
            **_pages_cache(spec, B, max_len, dtype)}


def _full_decode(spec, q, k, v, *, cache, pos, state=None, interpret=None):
    """Append k/v at ``pos`` and attend the whole cache, causal on
    original positions (the N=1-query-vs-long-cache path)."""
    qr, kr = _rope_qk(spec, q, k, pos[:, None])
    B, Hkv = kr.shape[0], kr.shape[1]
    bi = jnp.arange(B)[:, None]
    hi = jnp.arange(Hkv)[None, :]
    ck = cache["k"].at[bi, hi, pos[:, None]].set(
        kr[:, :, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bi, hi, pos[:, None]].set(
        v[:, :, 0].astype(cache["v"].dtype))
    o = full_attention(qr, ck, cv, causal=True, positions=pos[:, None],
                       logit_scale=spec.logit_scale)
    return o, {**cache, "k": ck, "v": cv}


def _local_decode(spec, q, k, v, *, cache, pos, state=None, interpret=None):
    """Blocked-local decode over the 2W ring: attend keys whose stored
    absolute position lies in blocks b-1, b of the query position."""
    qr, kr = _rope_qk(spec, q, k, pos[:, None])
    window = spec.window
    B, Hkv = kr.shape[0], kr.shape[1]
    S2 = cache["lk"].shape[2]
    slot = pos % S2
    bi = jnp.arange(B)[:, None]
    hi = jnp.arange(Hkv)[None, :]
    ck = cache["lk"].at[bi, hi, slot[:, None]].set(
        kr[:, :, 0].astype(cache["lk"].dtype))
    cv = cache["lv"].at[bi, hi, slot[:, None]].set(
        v[:, :, 0].astype(cache["lv"].dtype))
    cp = cache["lpos"].at[jnp.arange(B), slot].set(pos)
    lo = (pos // window - 1) * window      # start of block b-1
    valid = (cp >= jnp.maximum(lo, 0)[:, None]) & (cp >= 0) & \
            (cp <= pos[:, None])
    o = full_attention(qr, ck, cv, causal=False, pad_mask=valid,
                       logit_scale=spec.logit_scale)
    return o, {**cache, "lk": ck, "lv": cv, "lpos": cp}


def _route_token(q, mu, cache):
    """Stage 1 of cluster-paged decode, shared verbatim by the xla and
    pallas_paged paths (so their cache trajectories are identical by
    construction): normalize the token's routing vector, argmax it
    against the centroids, read the selected page's write counter."""
    r = normalize_routing(q)[:, :, 0]      # (B,Hr,dh)
    scores = jnp.einsum("bhd,hkd->bhk", r.astype(jnp.float32),
                        mu.astype(jnp.float32))
    c = jnp.argmax(scores, axis=-1)        # (B,Hr)
    plen = jnp.take_along_axis(cache["rlen"], c[:, :, None], axis=2)[..., 0]
    return r, c, plen


def _write_page_slot(cache, r, v0, c, plen):
    """Ring-overwrite the new token into slot plen % cap of page c —
    the one cache write of a decode step, shared by both paths."""
    B, Hr = c.shape
    cap = cache["rk"].shape[3]
    wslot = plen % cap
    bi = jnp.arange(B)[:, None]
    hi = jnp.arange(Hr)[None, :]
    ck = cache["rk"].at[bi, hi, c, wslot].set(r.astype(cache["rk"].dtype))
    cv = cache["rv"].at[bi, hi, c, wslot].set(v0.astype(cache["rv"].dtype))
    cl = cache["rlen"].at[bi, hi, c].set(plen + 1)
    return {**cache, "rk": ck, "rv": cv, "rlen": cl}


def _routing_decode(spec, q, k, v, *, cache, pos, state=None,
                    interpret=None):
    """Cluster-paged routing decode: the token routes to its argmax
    centroid and attends only that page (+ itself). ``state`` is the
    layer's centroid tree mu (Hr, kc, dh); q/v arrive un-roped with Hkv
    heads and are expanded to the routing head count here."""
    mu = state
    v = _expand_kv(v, spec.q_per_kv)
    _, _, _, dh = q.shape
    cap = cache["rk"].shape[3]
    r, c, plen = _route_token(q, mu, cache)
    sel = c[:, :, None, None, None]
    page_k = jnp.take_along_axis(cache["rk"], sel, axis=2)[:, :, 0]
    page_v = jnp.take_along_axis(cache["rv"], sel, axis=2)[:, :, 0]
    nvalid = jnp.minimum(plen, cap)        # (B,Hr)
    logits = jnp.einsum("bhd,bhcd->bhc", r, page_k).astype(jnp.float32)
    logits = logits / jnp.sqrt(dh)
    slot_ok = jnp.arange(cap)[None, None, :] < nvalid[..., None]
    logits = jnp.where(slot_ok, logits, _BIG_NEG)
    self_logit = (jnp.einsum("bhd,bhd->bh", r, r) /
                  jnp.sqrt(dh)).astype(jnp.float32)
    all_logits = jnp.concatenate([logits, self_logit[..., None]], -1)
    attn = jax.nn.softmax(all_logits, axis=-1)
    vals = jnp.concatenate([page_v, v[:, :, 0][:, :, None, :]], 2)
    o = jnp.einsum("bhc,bhcd->bhd", attn.astype(vals.dtype), vals)
    new_cache = _write_page_slot(cache, r, v[:, :, 0], c, plen)
    return o[:, :, None, :], new_cache


def _routing_decode_paged(spec, q, k, v, *, cache, pos, state=None,
                          interpret=None):
    """Paged-kernel routing decode: stage 1 and the ring-slot write are
    the exact XLA code the reference runs; the page attention itself is
    the Pallas kernel, which DMAs only the selected cluster page into
    VMEM through scalar-prefetched page tables (kernels.routing_decode)
    instead of materializing a gathered page copy in HBM."""
    from repro.kernels import ops as kops
    mu = state
    v = _expand_kv(v, spec.q_per_kv)
    r, c, plen = _route_token(q, mu, cache)
    o = kops.paged_routing_decode(r, v[:, :, 0], cache["rk"], cache["rv"],
                                  cache["rlen"], c, interpret=interpret)
    new_cache = _write_page_slot(cache, r, v[:, :, 0], c, plen)
    return o[:, :, None, :], new_cache


def _make_mixed_decode(routing_decode):
    """local+routing decode: ring-local reference half + the given
    routing decode fn (xla reference or the paged kernel)."""
    def decode(spec, q, k, v, *, cache, pos, state=None, interpret=None):
        (ql, kl, vl), (qr, _, vr) = _split_heads(spec, q, k, v)
        ring = {n: cache[n] for n in ("lk", "lv", "lpos")}
        o_l, ring = _local_decode(_local_subspec(spec), ql, kl, vl,
                                  cache=ring, pos=pos, interpret=interpret)
        pages = {n: cache[n] for n in ("rk", "rv", "rlen")}
        o_r, pages = routing_decode(_routing_subspec(spec), qr, None, vr,
                                    cache=pages, pos=pos, state=state,
                                    interpret=interpret)
        return jnp.concatenate([o_l, o_r], axis=1), {**ring, **pages}
    return decode


_mixed_decode = _make_mixed_decode(_routing_decode)
_mixed_decode_paged = _make_mixed_decode(_routing_decode_paged)


# ---------------------------------------------------------------------------
# Prefill cache fill
# ---------------------------------------------------------------------------
def _append_fill(spec, cache, q, k, v, *, positions, state=None):
    _, kr = _rope_qk(spec, q, k, positions)
    out = dict(cache)
    out["k"] = jax.lax.dynamic_update_slice(
        cache["k"], kr.astype(cache["k"].dtype), (0, 0, 0, 0))
    out["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
    return out


def _ring_fill(spec, cache, q, k, v, *, positions, state=None):
    """Place token t at ring slot t % 2W; keep the last 2W tokens."""
    B, N = positions.shape
    _, kr = _rope_qk(spec, q, k, positions)
    S2 = cache["lk"].shape[2]
    take = min(N, S2)
    tail_k = kr[:, :, -take:]
    tail_v = v[:, :, -take:]
    tail_pos = positions[:, -take:]
    slots = tail_pos % S2                                  # (B,take)
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(tail_k.shape[1])[None, :, None]
    si = slots[:, None, :]
    out = dict(cache)
    out["lk"] = cache["lk"].at[bi, hi, si].set(
        tail_k.astype(cache["lk"].dtype))
    out["lv"] = cache["lv"].at[bi, hi, si].set(
        tail_v.astype(cache["lv"].dtype))
    out["lpos"] = cache["lpos"].at[jnp.arange(B)[:, None], slots].set(
        tail_pos)
    return out


def _pages_fill(spec, cache, q, k, v, *, positions, state=None):
    """Route every prefix token to its argmax page, keeping the most
    recent ``cap`` per page at the ring slots sequential decode would
    have used (ring continuity)."""
    B = q.shape[0]
    vr = _expand_kv(v, spec.q_per_kv)
    r = normalize_routing(q)                               # (B,Hr,N,dh)
    kc, cap = cache["rk"].shape[2], cache["rk"].shape[3]
    Hr = r.shape[1]
    scores = jnp.einsum("bhnd,hkd->bhnk", r.astype(jnp.float32),
                        state.astype(jnp.float32))
    assign = jnp.argmax(scores, -1)                        # (B,Hr,N)
    memb = jax.nn.one_hot(assign, kc, dtype=jnp.int32)     # (B,Hr,N,kc)
    rank_from_end = jnp.cumsum(memb[:, :, ::-1], axis=2)[:, :, ::-1]
    rank_from_end = (rank_from_end * memb).max(-1)         # (B,Hr,N) 1-based
    keep = (rank_from_end >= 1) & (rank_from_end <= cap)
    counts = memb.sum(2)                                   # (B,Hr,kc)
    write_slot = jnp.where(
        keep,
        (jnp.take_along_axis(counts, assign, axis=2) % cap
         - rank_from_end) % cap,
        cap)                                               # cap = trash
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(Hr)[None, :, None]
    rk_pad = jnp.concatenate(
        [cache["rk"], jnp.zeros_like(cache["rk"][:, :, :, :1])], 3)
    rv_pad = jnp.concatenate(
        [cache["rv"], jnp.zeros_like(cache["rv"][:, :, :, :1])], 3)
    rk_pad = rk_pad.at[bi, hi, assign, write_slot].set(
        r.astype(rk_pad.dtype))
    rv_pad = rv_pad.at[bi, hi, assign, write_slot].set(
        vr.astype(rv_pad.dtype))
    out = dict(cache)
    out["rk"] = rk_pad[:, :, :, :cap]
    out["rv"] = rv_pad[:, :, :, :cap]
    out["rlen"] = counts
    return out


def _mixed_fill(spec, cache, q, k, v, *, positions, state=None):
    (ql, kl, vl), (qr, _, vr) = _split_heads(spec, q, k, v)
    out = _ring_fill(_local_subspec(spec), cache, ql, kl, vl,
                     positions=positions)
    out = _pages_fill(_routing_subspec(spec), out, qr, None, vr,
                      positions=positions, state=state)
    return out


# ---------------------------------------------------------------------------
# Registration
# ---------------------------------------------------------------------------
_RING_FILLS = {"lpos": -1}
_RING_AXES = {"lk": 2, "lv": 2}
_PAGE_AXES = {"rk": 2, "rv": 2, "rlen": 2}
_PAGE_LEAVES = ("rk", "rv")

APPEND_LAYOUT = CacheLayout(
    name="append", init=_append_cache, fill=_append_fill,
    head_axes={"k": 2, "v": 2})

RING_LAYOUT = CacheLayout(
    name="ring", init=_ring_cache, fill=_ring_fill,
    reset_values=_RING_FILLS, head_axes=_RING_AXES)

PAGES_LAYOUT = CacheLayout(
    name="pages", init=_pages_cache, fill=_pages_fill,
    head_axes=_PAGE_AXES, pageable_leaves=_PAGE_LEAVES,
    page_len_leaf="rlen")

MIXED_LAYOUT = CacheLayout(
    name="ring+pages", init=_mixed_cache, fill=_mixed_fill,
    reset_values=_RING_FILLS, head_axes={**_RING_AXES, **_PAGE_AXES},
    pageable_leaves=_PAGE_LEAVES, page_len_leaf="rlen")

registry.register(Backend(
    variant="full", impl="xla", apply=_full_xla_apply,
    decode=_full_decode, layout=APPEND_LAYOUT,
    caps=Capabilities(supports_decode=True, supports_mesh=True,
                      supports_pad_mask=True, supports_logit_scale=True,
                      supports_grad=True)))

# supports_positions=False: the flash kernel masks causality by row
# index — the positions-aware reference must serve packed/offset calls
registry.register(Backend(
    variant="full", impl="pallas", apply=_full_pallas_apply, priority=10,
    caps=Capabilities(supports_decode=False, supports_mesh=False,
                      supports_pad_mask=False, supports_positions=False,
                      supports_grad=True, needs_tpu=True)))

registry.register(Backend(
    variant="local", impl="xla", apply=_local_xla_apply,
    decode=_local_decode, layout=RING_LAYOUT,
    caps=Capabilities(supports_decode=True, supports_mesh=True,
                      supports_pad_mask=True, supports_grad=True)))

registry.register(Backend(
    variant="local", impl="pallas", apply=_local_pallas_apply, priority=10,
    caps=Capabilities(supports_decode=False, supports_mesh=False,
                      supports_pad_mask=False, supports_grad=True,
                      needs_tpu=True)))

registry.register(Backend(
    variant="routing", impl="xla", apply=_make_routing_apply("xla"),
    decode=_routing_decode, layout=PAGES_LAYOUT,
    caps=Capabilities(supports_decode=True, supports_mesh=True,
                      supports_pad_mask=True, supports_grad=True)))

# gather-free fused kernel: priority 20, so TPU auto-selection takes it
# over the reference; supports_grad via its custom VJP. supports_mesh=
# False like every Pallas backend: a GSPMD mesh call falls back to the
# reference; the shard_map train path (per-device programs, no mesh at
# attend) runs the kernel in distributed training (§9). No sequence cap:
# the kernel switches its memory plan at the VMEM residency budget
# (kernels.common.fused_paged_default) — whole-plane VMEM residency
# below it, member rows DMA'd from HBM above (VMEM bounded by the
# cluster size w, not N), so paper-scale N=8k–32k in 32 clusters stays
# fused forward and backward.
registry.register(Backend(
    variant="routing", impl="pallas_fused",
    apply=_make_routing_apply("pallas_fused"), priority=20,
    caps=Capabilities(supports_decode=False, supports_mesh=False,
                      supports_pad_mask=True, supports_grad=True,
                      needs_tpu=True)))

registry.register(Backend(
    variant="local+routing", impl="xla", apply=_make_mixed_apply("xla"),
    decode=_mixed_decode, layout=MIXED_LAYOUT,
    caps=Capabilities(supports_decode=True, supports_mesh=True,
                      supports_pad_mask=True, supports_grad=True)))

registry.register(Backend(
    variant="local+routing", impl="pallas_fused",
    apply=_make_mixed_apply("pallas_fused"), priority=20,
    caps=Capabilities(supports_decode=False, supports_mesh=False,
                      supports_pad_mask=True, supports_grad=True,
                      needs_tpu=True)))

# paged decode: fused apply plus the paged-decode kernel, so the serving
# hot path is Pallas too. Registered AFTER pallas_fused at the same
# priority 20 on purpose: resolve() keeps the first max on a tie, so
# apply calls still pick pallas_fused while decode (where fused declares
# supports_decode=False) lands here instead of the priority-0 xla
# reference. Shares the cluster-page layouts with xla — engines can
# prefill under one impl and decode under the other, and decode under a
# GSPMD mesh falls back to the reference like every Pallas backend.
registry.register(Backend(
    variant="routing", impl="pallas_paged",
    apply=_make_routing_apply("pallas_fused"),
    decode=_routing_decode_paged, layout=PAGES_LAYOUT, priority=20,
    caps=Capabilities(supports_decode=True, supports_mesh=False,
                      supports_pad_mask=True, supports_grad=True,
                      needs_tpu=True)))

registry.register(Backend(
    variant="local+routing", impl="pallas_paged",
    apply=_make_mixed_apply("pallas_fused"),
    decode=_mixed_decode_paged, layout=MIXED_LAYOUT, priority=20,
    caps=Capabilities(supports_decode=True, supports_mesh=False,
                      supports_pad_mask=True, supports_grad=True,
                      needs_tpu=True)))
