"""Paged routing decode — the Pallas kernel for the serving hot path.

Single-token decode for the routing variants attends one cluster page:
the decoded token's routing vector picks its argmax centroid and the
kernel scores it against that page's occupied slots (+ the token itself).
The XLA reference (`attn.backends._routing_decode`) materializes the
selected (B,Hr,1,cap,dh) page with `take_along_axis` — an HBM gather of
the whole page per step.

`paged_routing_decode` removes the gather with the same scalar-prefetch
page-table trick the fused train kernel uses (DESIGN.md §9): the selected
cluster ids (B,Hr) and the per-page length table `rlen` (B,Hr,kc) ride in
as scalar-prefetch operands (`PrefetchScalarGridSpec`, SMEM), and the
page BlockSpec's index map reads the cluster id to DMA exactly one
(cap,dh) page per (batch, head) grid step straight from the paged cache
into VMEM — no gathered copy ever reaches HBM. Slots at index >=
min(rlen, cap) are dead weight in the pull but masked to -1e9 before the
softmax, so garbage in unoccupied slots cannot leak into the output
(tests poison them to prove it).

Parity contract (gated in tests/test_routing_decode.py): stage 1
(routing-vector normalization, centroid argmax) and the ring-slot cache
write stay in XLA in the backend wrapper — literally the same code the
reference runs — so the cache trajectory is bit-identical by
construction, and greedy-decoded token streams are bit-identical over
long multi-step decode. The in-kernel attention mirrors the reference's
op sequence (dot in the promoted input dtype, f32 cast, divide by
sqrt(dh), occupancy mask, concat the self logit, `jax.nn.softmax` in
f32, concat values, dot), which pins the per-step attention output to
within a few float32 ulps of the reference (measured <= 2e-6 absolute);
exact bitwise equality of the float reductions is not promisable — XLA
compiles the same dot differently depending on surrounding program
context (verified: even jit(dynamic_slice + dot) differs from the eager
dot by 1 ulp on CPU), and on TPU the MXU accumulates differently from
an XLA einsum anyway. Because the only state fed forward between steps
is the cache (bitwise equal) and the sampled token (argmax, immune to
ulp noise), the ulp difference does not compound.

Grid: (B, Hr) — one grid step per (batch, head), blocks (1,1,cap,dh) for
the page and (1,1,dh) for the token vectors. cap*dh is a few KiB at
paper shapes (cap = routing window, 32..256), so the whole page fits
VMEM with no sequence-length cliff; decode cost per token is O(cap*dh)
per routing head regardless of context length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import default_interpret


def _dot(a, b, dims, dt):
    """dot_general accumulated in float32 (Mosaic's only accumulator)
    and rounded to ``dt`` — what the reference's einsum in ``dt``
    produces."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32).astype(dt)


def _decode_kernel(c_ref, rlen_ref, r_ref, v_ref, rk_ref, rv_ref, o_ref,
                   *, cap, dh):
    b = pl.program_id(0)
    h = pl.program_id(1)
    c = c_ref[b, h]
    plen = rlen_ref[b, h, c]
    nvalid = jnp.minimum(plen, cap)

    r = r_ref[0, 0]                    # (1, dh)
    page_k = rk_ref[0, 0, 0]           # (cap, dh) — the selected page
    page_v = rv_ref[0, 0, 0]

    # mirror the reference op-for-op: dot in the promoted input dtype,
    # THEN cast f32, THEN divide (mul-by-reciprocal would not be bitwise)
    s_dt = jnp.promote_types(r.dtype, page_k.dtype)
    logits = _dot(r.astype(s_dt), page_k.astype(s_dt), ((1,), (1,)), s_dt)
    logits = logits.astype(jnp.float32) / jnp.sqrt(dh)          # (1, cap)
    slot_ok = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1) < nvalid
    logits = jnp.where(slot_ok, logits, _NEG)
    # reference divides the self score in r.dtype before the f32 cast;
    # a dot (not mul+reduce) so the accumulation order matches einsum's
    self_logit = (_dot(r, r, ((1,), (1,)), r.dtype) /
                  jnp.sqrt(dh)).astype(jnp.float32)             # (1, 1)
    all_logits = jnp.concatenate([logits, self_logit], axis=1)  # (1,cap+1)
    attn = jax.nn.softmax(all_logits, axis=-1)

    v_new = v_ref[0, 0]                # (1, dh)
    vals_dt = jnp.promote_types(page_v.dtype, v_new.dtype)
    vals = jnp.concatenate([page_v.astype(vals_dt),
                            v_new.astype(vals_dt)], axis=0)     # (cap+1,dh)
    o = _dot(attn.astype(vals_dt), vals, ((1,), (0,)), vals_dt)  # (1, dh)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def paged_routing_decode(r, v_new, rk, rv, rlen, cluster, interpret=None):
    """One decoded token of routed attention over the cluster-paged cache.

    r:       (B,Hr,dh)  normalized routing vector of the new token
             (shared-QK: it is both the query and its own key)
    v_new:   (B,Hr,dh)  the new token's value (kv heads pre-expanded)
    rk/rv:   (B,Hr,kc,cap,dh)  paged cache of routing keys / values
    rlen:    (B,Hr,kc)  int32 per-page write counters (>= cap => full ring)
    cluster: (B,Hr)     int32 argmax page id of the new token

    Returns o (B,Hr,dh) — softmax over the page's min(rlen,cap) occupied
    slots plus the token itself. Pure read: the caller owns the ring-slot
    cache write (kept in XLA so the cache trajectory is shared with the
    reference backend). ``interpret=None`` derives from the platform.
    """
    B, Hr, dh = r.shape
    kc, cap = rk.shape[2], rk.shape[3]
    tok_at = lambda b, h, *_: (b, h, 0, 0)
    # the paged-attention move: the index map reads the prefetched
    # cluster id, so only the selected page is ever DMA'd to VMEM
    page_at = lambda b, h, c_ref, rlen_ref: (b, h, c_ref[b, h], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hr),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), tok_at),         # r
            pl.BlockSpec((1, 1, 1, dh), tok_at),         # v_new
            pl.BlockSpec((1, 1, 1, cap, dh), page_at),   # rk page
            pl.BlockSpec((1, 1, 1, cap, dh), page_at),   # rv page
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh), tok_at))
    out_dtype = jnp.promote_types(rv.dtype, v_new.dtype)
    o = pl.pallas_call(
        functools.partial(_decode_kernel, cap=cap, dh=dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hr, 1, dh), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=default_interpret(interpret),
    )(cluster.astype(jnp.int32), rlen.astype(jnp.int32),
      r[:, :, None], v_new[:, :, None], rk, rv)
    return o[:, :, 0]
