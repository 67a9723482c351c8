"""Unified transformer stack for every assigned architecture family.

The stack is a list of *segments*; each segment is a repeating *pattern* of
layer specs scanned `n_groups` times with `jax.lax.scan` (keeps HLO size
independent of depth — critical for 48-layer 400B dry-runs), plus remat at
group granularity. k-means centroid state for routing layers is threaded
through the scan as xs/ys (functional state, no mutation).

Layer kinds:
  attn    norm -> self-attention (full|local|routing|local+routing) -> norm -> FFN
  moe     same but FFN is the MoE layer
  cross   norm -> cross-attention to image embeddings -> norm -> FFN (VLM)
  ssd     norm -> mamba2 SSD mixer (no FFN; d_ff=0)
  rglru   norm -> RG-LRU mixer -> norm -> FFN (Griffin block)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import attn as attn_api
from repro.attn.spec import head_split, spec_for_layer, variant_for_layer
from repro.configs.base import ModelConfig
from repro.core.attention import full_attention
from repro.core.kmeans import init_kmeans
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.obs.trace import span

AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")


@dataclass(frozen=True)
class LayerSpec:
    kind: str                 # attn | moe | cross | ssd | rglru
    attn: str = "full"        # attention backend for attn/moe/cross


# ---------------------------------------------------------------------------
# Segment construction
# ---------------------------------------------------------------------------
def per_layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    Lr = cfg.num_layers
    attn_mode = lambda i: variant_for_layer(cfg, i)  # noqa: E731
    specs = []
    for i in range(Lr):
        if cfg.family == "ssm":
            specs.append(LayerSpec("ssd"))
        elif cfg.family == "hybrid":
            pat = cfg.hybrid_pattern or ("rglru", "rglru", "attn")
            kind = pat[i % len(pat)]
            specs.append(LayerSpec(kind, attn_mode(i) if kind == "attn"
                                   else "full"))
        elif cfg.family == "moe":
            kind = "moe" if i % cfg.moe_interleave == 0 else "attn"
            specs.append(LayerSpec(kind, attn_mode(i)))
        elif cfg.family == "vlm":
            kind = "cross" if (i + 1) % 5 == 0 else "attn"
            specs.append(LayerSpec(kind, attn_mode(i)))
        else:  # dense / encoder
            specs.append(LayerSpec("attn", attn_mode(i)))
    return specs


def build_segments(cfg: ModelConfig) -> List[Tuple[Tuple[LayerSpec, ...], int]]:
    """Compress the per-layer spec list into (pattern, n_groups) segments."""
    specs = per_layer_specs(cfg)
    period = {"moe": cfg.moe_interleave, "vlm": 5,
              "hybrid": len(cfg.hybrid_pattern or ("rglru", "rglru", "attn"))
              }.get(cfg.family, 1)
    segments: List[Tuple[Tuple[LayerSpec, ...], int]] = []
    i = 0
    while i < len(specs):
        # longest run of repeats of specs[i:i+period]
        pat = tuple(specs[i:i + period])
        g = 0
        while (i + (g + 1) * len(pat) <= len(specs)
               and tuple(specs[i + g * len(pat):i + (g + 1) * len(pat)]) == pat):
            g += 1
        if g == 0:                       # tail shorter than period
            pat = tuple(specs[i:])
            g = 1
        segments.append((pat, g))
        i += g * len(pat)
    return segments


# head_split (the paper's local/routing split) now lives in
# repro.attn.spec and is re-exported above for existing importers.


def where_active(active: jax.Array, new_tree, old_tree, batch_axis: int = 1):
    """Row-select between two cache pytrees along the slot (batch) axis.

    Continuous-batching decode runs every pool slot through the stack each
    step; rows where ``active`` is False must be exact no-ops so a finished
    or free slot's cache is untouched until it is re-admitted. ``active`` is
    a (B,) bool vector; leaves are indexed (…, B, …) at ``batch_axis``.
    """
    def sel(n, o):
        shape = [1] * n.ndim
        shape[batch_axis] = -1
        return jnp.where(active.reshape(shape), n, o)
    return jax.tree.map(sel, new_tree, old_tree)


# ---------------------------------------------------------------------------
# Layer init
# ---------------------------------------------------------------------------
def init_layer(key, spec: LayerSpec, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg.d_model, cfg.norm, dt)}
    if spec.kind in ("attn", "moe", "cross"):
        p["attn"] = L.init_attn_proj(ks[0], cfg)
        p["ln2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        if spec.kind == "moe":
            p["ffn"] = moe_mod.init_moe(ks[1], cfg)
        else:
            p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dt)
        if spec.kind == "cross":
            p["xgate_attn"] = jnp.zeros((), jnp.float32)
            p["xgate_ffn"] = jnp.zeros((), jnp.float32)
    elif spec.kind == "ssd":
        p["mixer"] = ssm_mod.init_ssd(ks[0], cfg)
    elif spec.kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg)
        p["ln2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def layer_kstate(key, spec: LayerSpec, cfg: ModelConfig):
    """Centroid state for a layer, or None if no routing heads."""
    if spec.kind not in ("attn", "moe", "cross") or "routing" not in spec.attn:
        return None
    if spec.attn == "routing":
        Hr = cfg.num_heads
    else:
        _, Hr, _, _ = head_split(cfg)
    return init_kmeans(key, Hr, cfg.routing.num_clusters, cfg.head_dim_).mu


# ---------------------------------------------------------------------------
# Attention dispatch — one call into repro.attn; variant math, rope
# policy, head splitting, and backend selection all live behind
# attn.attend (DESIGN.md §8)
# ---------------------------------------------------------------------------
def self_attention(p, h, cfg: ModelConfig, mode: str, kmu,
                   positions, pad_mask, update_state, impl=None, mesh=None,
                   needs_grad=False):
    """h: (B,N,d) -> ((B,N,d), new_kmu, stats). ``stats`` is the
    obs.RoutingStats aux of a routing variant with RoutingConfig.stats
    on, else None."""
    with span("model/attention_proj"):
        q, k, v = L.qkv_project(p, h, cfg, positions, rope=False)
    out = attn_api.attend(spec_for_layer(cfg, mode), q, k, v, state=kmu,
                          positions=positions, pad_mask=pad_mask,
                          update_state=update_state, impl=impl, mesh=mesh,
                          needs_grad=needs_grad)
    with span("model/attention_proj"):
        o = L.out_project(p, out.out)
    return o, out.state, out.stats


def cross_attention(p, h, image_embeds, cfg: ModelConfig, pad_mask=None):
    """Text queries attend to image tokens (no causal mask, no rope)."""
    B, N, _ = h.shape
    q, _, _ = L.qkv_project(p, h, cfg, rope=False)
    dh, Hkv = cfg.head_dim_, cfg.num_kv_heads
    M = image_embeds.shape[1]
    k = (image_embeds @ p["wk"]).reshape(B, M, Hkv, dh).transpose(0, 2, 1, 3)
    v = (image_embeds @ p["wv"]).reshape(B, M, Hkv, dh).transpose(0, 2, 1, 3)
    o = full_attention(q, k, v, causal=False)
    return L.out_project(p, o)


# ---------------------------------------------------------------------------
# Layer apply
# ---------------------------------------------------------------------------
def _dropout(x, rate, rng):
    if rng is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def apply_layer(spec: LayerSpec, p, kmu, x, cfg: ModelConfig, *,
                positions=None, pad_mask=None, image_embeds=None,
                update_state=True, impl=None, moe_impl="einsum",
                drop_rng=None, mesh=None, needs_grad=False):
    aux = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    new_kmu = kmu
    rngs = (jax.random.split(drop_rng, 2) if drop_rng is not None
            else (None, None))
    if spec.kind in ("attn", "moe", "cross"):
        # spans (repro.obs) at the sublayer boundaries: norms, residual
        # adds and dropout go with the projections or the FFN they wrap
        with span("model/attention_proj"):
            h = L.apply_norm(p["ln1"], x, cfg.norm)
        if spec.kind == "cross":
            a = cross_attention(p["attn"], h, image_embeds, cfg)
            a = a * jnp.tanh(p["xgate_attn"]).astype(a.dtype)
        else:
            a, new_kmu, a_stats = self_attention(
                p["attn"], h, cfg, spec.attn, kmu, positions, pad_mask,
                update_state, impl, mesh=mesh, needs_grad=needs_grad)
            if a_stats is not None:
                # rides in aux (popped by apply_stack / prefill into the
                # scan ys; NOT one of the fixed AUX_KEYS scalars)
                aux["routing_stats"] = a_stats
        with span("model/attention_proj"):
            x = x + _dropout(a, cfg.dropout, rngs[0])
        with span("model/ffn"):
            h2 = L.apply_norm(p["ln2"], x, cfg.norm)
            if spec.kind == "moe":
                ff, moe_aux = moe_mod.apply_moe(p["ffn"], h2, cfg,
                                                impl=moe_impl)
                aux.update({k: jnp.asarray(v, jnp.float32)
                            for k, v in moe_aux.items()})
            else:
                ff = L.apply_mlp(p["ffn"], h2, cfg.act)
                if spec.kind == "cross":
                    ff = ff * jnp.tanh(p["xgate_ffn"]).astype(ff.dtype)
            x = x + _dropout(ff, cfg.dropout, rngs[1])
    elif spec.kind == "ssd":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, _ = ssm_mod.apply_ssd(p["mixer"], h, cfg)
        x = x + y
    elif spec.kind == "rglru":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, _ = rglru_mod.apply_rglru(p["mixer"], h, cfg)
        x = x + y
        h2 = L.apply_norm(p["ln2"], x, cfg.norm)
        x = x + _dropout(L.apply_mlp(p["ffn"], h2, cfg.act), cfg.dropout,
                         rngs[1])
    return x, new_kmu, aux


# ---------------------------------------------------------------------------
# Stack init / apply (scan over segment groups)
# ---------------------------------------------------------------------------
def init_stack(key, cfg: ModelConfig):
    segments = build_segments(cfg)
    seg_params, seg_kstate = [], []
    for si, (pattern, G) in enumerate(segments):
        key, sk = jax.random.split(key)
        gkeys = jax.random.split(sk, G)

        def init_group(k, pattern=pattern):
            ks = jax.random.split(k, 2 * len(pattern))
            params = tuple(init_layer(ks[2 * i], s, cfg)
                           for i, s in enumerate(pattern))
            kst = {str(i): layer_kstate(ks[2 * i + 1], s, cfg)
                   for i, s in enumerate(pattern)
                   if layer_kstate(ks[2 * i + 1], s, cfg) is not None}
            return params, kst

        params, kst = jax.vmap(init_group)(gkeys)
        seg_params.append(params)
        seg_kstate.append(kst)
    return seg_params, seg_kstate


def apply_stack(seg_params, seg_kstate, x, cfg: ModelConfig, *,
                positions=None, pad_mask=None, image_embeds=None,
                update_state=True, impl=None, moe_impl="einsum",
                remat="none", drop_rng=None,
                constrain_fn: Optional[Callable] = None, mesh=None,
                needs_grad=False):
    segments = build_segments(cfg)
    aux_tot = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    new_seg_kstate = []
    seg_stats = []
    constrain = constrain_fn or (lambda t: t)
    # fsdp prefetch (dist/sharding.make_constrain_fn): re-constrain the
    # group's weight slice to its gathered (TP-only) layout at group entry,
    # pinning the zero-3 all-gather to one schedulable point per group
    gather = getattr(constrain, "gather_params", None)
    # constrain the embedding output too: with sequence parallelism the
    # residual stream must enter the first scan group already seq-sharded,
    # or GSPMD keeps a replicated copy alive until the first group boundary
    x = constrain(x)
    layer_counter = 0
    for si, (pattern, G) in enumerate(segments):

        def group_fn(x, xs, pattern=pattern, base=layer_counter):
            p_group, k_group, gi = xs
            if gather is not None:
                p_group = gather(p_group)
            aux_g = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
            new_k = {}
            stats_g = {}
            for i, spec in enumerate(pattern):
                rng_i = None
                if drop_rng is not None and cfg.dropout > 0:
                    rng_i = jax.random.fold_in(
                        jax.random.fold_in(drop_rng, base + i), gi)
                x, nk, aux_i = apply_layer(
                    spec, p_group[i], k_group.get(str(i)), x, cfg,
                    positions=positions, pad_mask=pad_mask,
                    image_embeds=image_embeds, update_state=update_state,
                    impl=impl, moe_impl=moe_impl, drop_rng=rng_i,
                    mesh=mesh, needs_grad=needs_grad)
                if str(i) in k_group:
                    new_k[str(i)] = nk
                st = aux_i.pop("routing_stats", None)
                if st is not None:
                    # per-layer stats leave the scan as stacked ys (a
                    # tracer cannot escape the scan body any other way);
                    # leaves come back with a leading (G,) group axis
                    stats_g[str(i)] = st
                aux_g = {k: aux_g[k] + aux_i[k] for k in AUX_KEYS}
            return constrain(x), new_k, stats_g, aux_g

        if remat == "full":
            group_fn = jax.checkpoint(group_fn, static_argnums=())
        elif remat == "save_dots":
            group_fn = jax.checkpoint(
                group_fn,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        def scan_body(carry, xs):
            x, aux = carry
            x, new_k, stats_g, aux_g = group_fn(x, xs)
            aux = {k: aux[k] + aux_g[k] for k in AUX_KEYS}
            return (x, aux), (new_k, stats_g)

        xs = (seg_params[si], seg_kstate[si], jnp.arange(G))
        # the loop's own work (slicing each group's weights, stacking
        # outputs and weight gradients) is named by this span; the
        # layers' spans inside it name theirs
        with span("model/stack"):
            (x, aux_tot), (new_k, seg_st) = jax.lax.scan(
                scan_body, (x, aux_tot), xs)
        new_seg_kstate.append(new_k)
        seg_stats.append(seg_st)
        layer_counter += G * len(pattern)
    if any(seg_st for seg_st in seg_stats):
        # list over segments of {layer: RoutingStats}, leaves stacked
        # over scan groups (G, ...); absent entirely when stats are off
        # so the aux pytree (and with it the HLO) is unchanged
        aux_tot = dict(aux_tot)
        aux_tot["routing_stats"] = seg_stats
    return x, new_seg_kstate, aux_tot
