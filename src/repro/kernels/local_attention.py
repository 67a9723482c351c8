"""Blocked local (sliding-window) attention — Pallas TPU kernel,
differentiable.

One grid point per (batch·head, query block). The query block attends its
own block and the previous one (+ next in encoder mode) — the paper's local
attention. Both KV tiles are index-mapped views of the same HBM array
(block b-1 clamps to 0 and is masked for b == 0), so the softmax over the
concatenated 2w (3w) keys happens entirely in VMEM in one shot: for w <= 512
the (w x 2w) fp32 score tile is ~2 MiB, comfortably inside VMEM — no
running-softmax needed.

Backward (``jax.custom_vjp``): the forward also emits per-row lse stats;
the dq kernel mirrors the forward exactly (recompute p = exp(s - lse),
dq = ds @ K_cat). The dk/dv kernel inverts the window: key block b is
attended by query blocks {b, b+1} (causal; {b-1, b, b+1} in encoder mode),
so it index-maps those q/do/lse/D blocks in (clamped at the edges, masked
via intended positions) and accumulates both contributions in one grid
point. dk/dv come out per *query* head and are group-summed to the GQA kv
heads in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import default_interpret


def _kernel(q_ref, kp_ref, kc_ref, kn_ref, vp_ref, vc_ref, vn_ref, o_ref,
            lse_ref, *, w, causal, scale, nb):
    b = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                    # (w, dh)
    ks = [kp_ref[0], kc_ref[0]] + ([kn_ref[0]] if not causal else [])
    vs = [vp_ref[0], vc_ref[0]] + ([vn_ref[0]] if not causal else [])
    k = jnp.concatenate([x.astype(jnp.float32) for x in ks], axis=0)
    v = jnp.concatenate([x.astype(jnp.float32) for x in vs], axis=0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    cw = k.shape[0]
    pos_q = b * w + jax.lax.broadcasted_iota(jnp.int32, (w, cw), 0)
    off = jax.lax.broadcasted_iota(jnp.int32, (w, cw), 1)
    pos_k = (b - 1) * w + off                           # prev tile then own
    keep = (pos_k >= 0) & (pos_k < nb * w)
    if causal:
        keep &= pos_q >= pos_k
    s = jnp.where(keep, s, _NEG)
    m = s.max(-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jax.lax.dot_general(p / l, v, (((1,), (0,)), ((), ())))
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _bwd_dq_kernel(q_ref, kp_ref, kc_ref, kn_ref, vp_ref, vc_ref, vn_ref,
                   do_ref, lse_ref, dsum_ref, dq_ref, *, w, causal, scale,
                   nb):
    b = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    ks = [kp_ref[0], kc_ref[0]] + ([kn_ref[0]] if not causal else [])
    vs = [vp_ref[0], vc_ref[0]] + ([vn_ref[0]] if not causal else [])
    k = jnp.concatenate([x.astype(jnp.float32) for x in ks], axis=0)
    v = jnp.concatenate([x.astype(jnp.float32) for x in vs], axis=0)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    dsum = dsum_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    cw = k.shape[0]
    pos_q = b * w + jax.lax.broadcasted_iota(jnp.int32, (w, cw), 0)
    off = jax.lax.broadcasted_iota(jnp.int32, (w, cw), 1)
    pos_k = (b - 1) * w + off
    keep = (pos_k >= 0) & (pos_k < nb * w)
    if causal:
        keep &= pos_q >= pos_k
    p = jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - dsum[:, None]) * scale
    dq_ref[0] = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))


def _bwd_dkv_kernel(k_ref, v_ref, *refs, w, causal, scale, nb, deltas):
    """Key block b gathers contributions from the q blocks that attend it
    (b + delta for delta in ``deltas``); edge blocks are clamped by the
    index map and neutralized by the intended-position mask."""
    b = pl.program_id(1)
    q_refs, do_refs, lse_refs, dsum_refs = (
        refs[0:len(deltas)], refs[len(deltas):2 * len(deltas)],
        refs[2 * len(deltas):3 * len(deltas)],
        refs[3 * len(deltas):4 * len(deltas)])
    dk_ref, dv_ref = refs[4 * len(deltas):]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dk = jnp.zeros_like(k)
    dv = jnp.zeros_like(v)
    pos_k = b * w + jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    for d, q_r, do_r, lse_r, dsum_r in zip(deltas, q_refs, do_refs,
                                           lse_refs, dsum_refs):
        q = q_r[0].astype(jnp.float32)
        do = do_r[0].astype(jnp.float32)
        lse = lse_r[0, 0]
        dsum = dsum_r[0, 0]
        # intended (unclamped) query positions: rows outside [0, nb*w)
        # belong to a block that does not exist and mask to zero
        pos_q = (b + d) * w + jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        keep = (pos_q >= 0) & (pos_q < nb * w)
        if causal:
            keep &= pos_q >= pos_k
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        p = jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
        dv += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - dsum[:, None]) * scale
        dk += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))
    dk_ref[0] = dk
    dv_ref[0] = dv


def _shapes(q, k):
    B, H, N, dh = q.shape
    Hkv = k.shape[1]
    return B, H, Hkv, N, dh


def _kv_at(H, Hkv, nb, delta):
    g = H // Hkv

    def index(bh, b):
        kvh = (bh // H) * Hkv + (bh % H) // g
        return (kvh, jnp.clip(b + delta, 0, nb - 1), 0)
    return index


def _q_at(nb, delta):
    def index(bh, b):
        return (bh, jnp.clip(b + delta, 0, nb - 1), 0)
    return index


def _r_at(nb, delta):
    def index(bh, b):
        return (bh, 0, jnp.clip(b + delta, 0, nb - 1))
    return index


def _fwd_call(q, k, v, w, causal, interpret):
    B, H, Hkv, N, dh = _shapes(q, k)
    nb = N // w
    qf = q.reshape(B * H, N, dh)
    kf = k.reshape(B * Hkv, N, dh)
    vf = v.reshape(B * Hkv, N, dh)
    kv_spec = lambda d: pl.BlockSpec((1, w, dh), _kv_at(H, Hkv, nb, d))
    out, lse = pl.pallas_call(
        functools.partial(_kernel, w=w, causal=causal,
                          scale=1.0 / (dh ** 0.5), nb=nb),
        grid=(B * H, nb),
        in_specs=[
            pl.BlockSpec((1, w, dh), lambda bh, b: (bh, b, 0)),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
        ],
        out_specs=[
            pl.BlockSpec((1, w, dh), lambda bh, b: (bh, b, 0)),
            pl.BlockSpec((1, 1, w), lambda bh, b: (bh, 0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, N, dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, kf, kf, vf, vf, vf)
    return out.reshape(B, H, N, dh), lse


def _bwd_call(q, k, v, lse, out, do, w, causal, interpret):
    B, H, Hkv, N, dh = _shapes(q, k)
    g = H // Hkv
    nb = N // w
    qf = q.reshape(B * H, N, dh)
    kf = k.reshape(B * Hkv, N, dh)
    vf = v.reshape(B * Hkv, N, dh)
    dof = do.reshape(B * H, N, dh)
    dsum = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dsum = dsum.reshape(B * H, 1, N)
    scale = 1.0 / (dh ** 0.5)
    params = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    kv_spec = lambda d: pl.BlockSpec((1, w, dh), _kv_at(H, Hkv, nb, d))
    q_spec = lambda d: pl.BlockSpec((1, w, dh), _q_at(nb, d))
    r_spec = lambda d: pl.BlockSpec((1, 1, w), _r_at(nb, d))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, w=w, causal=causal, scale=scale,
                          nb=nb),
        grid=(B * H, nb),
        in_specs=[
            q_spec(0),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            q_spec(0), r_spec(0), r_spec(0),
        ],
        out_specs=pl.BlockSpec((1, w, dh), lambda bh, b: (bh, b, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, kf, kf, vf, vf, vf, dof, lse, dsum)

    deltas = (0, 1) if causal else (-1, 0, 1)
    dkv_in = ([kv_spec(0), kv_spec(0)]
              + [q_spec(d) for d in deltas]
              + [q_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas])
    dkv_ops = ([kf, vf] + [qf] * len(deltas) + [dof] * len(deltas)
               + [lse] * len(deltas) + [dsum] * len(deltas))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, w=w, causal=causal, scale=scale,
                          nb=nb, deltas=deltas),
        grid=(B * H, nb),
        in_specs=dkv_in,
        out_specs=[
            pl.BlockSpec((1, w, dh), lambda bh, b: (bh, b, 0)),
            pl.BlockSpec((1, w, dh), lambda bh, b: (bh, b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
            jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(*dkv_ops)

    dq = dq.reshape(B, H, N, dh).astype(q.dtype)
    dk = dk.reshape(B, Hkv, g, N, dh).sum(2).astype(k.dtype)
    dv = dv.reshape(B, Hkv, g, N, dh).sum(2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _local(w, causal, interpret, q, k, v):
    out, _ = _fwd_call(q, k, v, w, causal, interpret)
    return out


def _local_fwd(w, causal, interpret, q, k, v):
    out, lse = _fwd_call(q, k, v, w, causal, interpret)
    return out, (q, k, v, out, lse)


def _local_bwd(w, causal, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd_call(q, k, v, lse, out, do, w, causal, interpret)


_local.defvjp(_local_fwd, _local_bwd)


def local_attention_kernel(q, k, v, window, causal=True, interpret=None):
    """q: (B,H,N,dh); k,v: (B,Hkv,N,dh); N % window == 0. Differentiable."""
    N = q.shape[2]
    w = min(window, N)
    assert N % w == 0, (N, w)
    return _local(int(w), bool(causal), default_interpret(interpret),
                  q, k, v)
