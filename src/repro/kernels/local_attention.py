"""Blocked local (sliding-window) attention — Pallas TPU kernel,
differentiable.

The paper's local attention: each block of w queries attends its own
block and the previous one (+ next in encoder mode). The KV tiles are
index-mapped views of the same HBM array (block b-1 clamps to 0 and is
masked for b == 0), so the softmax over the concatenated 2w (3w) keys
happens entirely in VMEM in one shot — no running softmax.

Grid: (batch·head, window block × its query sub-tiles). A query sub-tile
of ``sub_tile(w, dh)`` rows meets the whole (w, dh) key/value blocks, so
VMEM is O(rows·w), not O(w²): for w <= 512 the sub-tile is the whole
window (a (w x 2w) fp32 score tile of ~2 MiB, one grid step a block);
at w = 2048 it is 128 rows against 4096 keys. The key/value blocks'
index does not change across a block's sub-tiles, so they are fetched
once a block.

Backward (``jax.custom_vjp``): the forward also emits per-row lse stats;
the dq kernel mirrors the forward exactly (recompute p = exp(s - lse),
dq = ds @ K_cat). The dk/dv kernel inverts the window: a key sub-tile of
block b is attended by query blocks {b, b+1} (causal; {b-1, b, b+1} in
encoder mode), so it index-maps those whole q/do/lse/D blocks in
(clamped at the edges, masked via intended positions) and accumulates
their contributions in one grid point. dk/dv come out per *query* head
and are group-summed to the GQA kv heads in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import default_interpret

# float32 bytes a sub-tile's score rows (and its q row) may take
_SUB_TILE_BYTES = 4 << 20
# scoped VMEM of a sub-tiled window's kernels: the whole (w, dh) blocks,
# double-buffered at 128 lanes, and the concatenated 2w keys and values
# beside the score tiles take ~16 MiB at w = 2048, v5e's default limit
_TILED_VMEM_BYTES = 32 << 20


def _tiles(nq):
    """The window block of grid step ``t``: its ``nq`` sub-tiles are
    consecutive steps, and sub-tile rows start at ``t * rows``."""
    return (lambda t: t) if nq == 1 else (lambda t: t // nq)


def _kernel(q_ref, kp_ref, kc_ref, kn_ref, vp_ref, vc_ref, vn_ref, o_ref,
            lse_ref, *, w, causal, scale, nb, nq):
    t = pl.program_id(1)
    b = _tiles(nq)(t)
    q = q_ref[0].astype(jnp.float32)                    # (bq, dh)
    bq = q.shape[0]
    ks = [kp_ref[0], kc_ref[0]] + ([kn_ref[0]] if not causal else [])
    vs = [vp_ref[0], vc_ref[0]] + ([vn_ref[0]] if not causal else [])
    k = jnp.concatenate([x.astype(jnp.float32) for x in ks], axis=0)
    v = jnp.concatenate([x.astype(jnp.float32) for x in vs], axis=0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    cw = k.shape[0]
    pos_q = t * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, cw), 0)
    off = jax.lax.broadcasted_iota(jnp.int32, (bq, cw), 1)
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
                   nb, nq):
    t = pl.program_id(1)
    b = _tiles(nq)(t)
    q = q_ref[0].astype(jnp.float32)
    bq = q.shape[0]
    ks = [kp_ref[0], kc_ref[0]] + ([kn_ref[0]] if not causal else [])
    vs = [vp_ref[0], vc_ref[0]] + ([vn_ref[0]] if not causal else [])
    k = jnp.concatenate([x.astype(jnp.float32) for x in ks], axis=0)
    v = jnp.concatenate([x.astype(jnp.float32) for x in vs], axis=0)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    dsum = dsum_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    cw = k.shape[0]
    pos_q = t * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, cw), 0)
    off = jax.lax.broadcasted_iota(jnp.int32, (bq, cw), 1)
    pos_k = (b - 1) * w + off
    keep = (pos_k >= 0) & (pos_k < nb * w)
    if causal:
        keep &= pos_q >= pos_k
    p = jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - dsum[:, None]) * scale
    dq_ref[0] = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))


def _bwd_dkv_kernel(k_ref, v_ref, *refs, w, causal, scale, nb, nq, deltas):
    """Key sub-tile ``t`` of window block b gathers contributions from
    the whole q blocks that attend it (b + delta for delta in
    ``deltas``); edge blocks are clamped by the index map and
    neutralized by the intended-position mask."""
    t = pl.program_id(1)
    b = _tiles(nq)(t)
    q_refs, do_refs, lse_refs, dsum_refs = (
        refs[0:len(deltas)], refs[len(deltas):2 * len(deltas)],
        refs[2 * len(deltas):3 * len(deltas)],
        refs[3 * len(deltas):4 * len(deltas)])
    dk_ref, dv_ref = refs[4 * len(deltas):]
    k = k_ref[0].astype(jnp.float32)                    # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    bk = k.shape[0]
    dk = jnp.zeros_like(k)
    dv = jnp.zeros_like(v)
    pos_k = t * bk + jax.lax.broadcasted_iota(jnp.int32, (w, bk), 1)
    for d, q_r, do_r, lse_r, dsum_r in zip(deltas, q_refs, do_refs,
                                           lse_refs, dsum_refs):
        q = q_r[0].astype(jnp.float32)
        do = do_r[0].astype(jnp.float32)
        lse = lse_r[0, 0]
        dsum = dsum_r[0, 0]
        # intended (unclamped) query positions: rows outside [0, nb*w)
        # belong to a block that does not exist and mask to zero
        pos_q = (b + d) * w + jax.lax.broadcasted_iota(jnp.int32, (w, bk), 0)
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


def sub_tile(w: int, dh: int) -> int:
    """Rows of a query sub-tile (forward, dq) and of a key sub-tile
    (dk/dv): the whole window while its float32 score rows against the
    2w keys, and its q row, fit ``_SUB_TILE_BYTES``; else the window
    halved until they do, to no less than 128 rows (the lane tile of the
    row-stat blocks)."""
    rows = w
    while rows * (2 * w + dh) * 4 > _SUB_TILE_BYTES and rows % 256 == 0:
        rows //= 2
    return rows


def _params(w, rows):
    """Compiler parameters: the whole-window grid keeps the default
    scoped VMEM; a sub-tiled one takes ``_TILED_VMEM_BYTES``."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=None if rows == w else _TILED_VMEM_BYTES)


def _kv_at(H, Hkv, nq, nb, delta):
    g = H // Hkv
    block = _tiles(nq)

    def index(bh, t):
        kvh = (bh // H) * Hkv + (bh % H) // g
        return (kvh, jnp.clip(block(t) + delta, 0, nb - 1), 0)
    return index


def _q_at(nq, nb, delta):
    block = _tiles(nq)

    def index(bh, t):
        return (bh, jnp.clip(block(t) + delta, 0, nb - 1), 0)
    return index


def _r_at(nq, nb, delta):
    block = _tiles(nq)

    def index(bh, t):
        return (bh, 0, jnp.clip(block(t) + delta, 0, nb - 1))
    return index


def _fwd_call(q, k, v, w, causal, interpret):
    B, H, Hkv, N, dh = _shapes(q, k)
    nb = N // w
    bq = sub_tile(w, dh)
    nq = w // bq
    qf = q.reshape(B * H, N, dh)
    kf = k.reshape(B * Hkv, N, dh)
    vf = v.reshape(B * Hkv, N, dh)
    kv_spec = lambda d: pl.BlockSpec((1, w, dh), _kv_at(H, Hkv, nq, nb, d))
    out, lse = pl.pallas_call(
        functools.partial(_kernel, w=w, causal=causal,
                          scale=1.0 / (dh ** 0.5), nb=nb, nq=nq),
        grid=(B * H, nb * nq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, t: (bh, t, 0)),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, t: (bh, t, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, t: (bh, 0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, N, dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, N), jnp.float32),
        ],
        compiler_params=_params(w, bq),
        interpret=interpret,
    )(qf, kf, kf, kf, vf, vf, vf)
    return out.reshape(B, H, N, dh), lse


def _bwd_call(q, k, v, lse, out, do, w, causal, interpret):
    B, H, Hkv, N, dh = _shapes(q, k)
    g = H // Hkv
    nb = N // w
    rows = sub_tile(w, dh)
    nq = w // rows
    qf = q.reshape(B * H, N, dh)
    kf = k.reshape(B * Hkv, N, dh)
    vf = v.reshape(B * Hkv, N, dh)
    dof = do.reshape(B * H, N, dh)
    dsum = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dsum = dsum.reshape(B * H, 1, N)
    scale = 1.0 / (dh ** 0.5)
    params = _params(w, rows)
    kv_spec = lambda d: pl.BlockSpec((1, w, dh), _kv_at(H, Hkv, nq, nb, d))
    q_spec = lambda d: pl.BlockSpec((1, w, dh), _q_at(nq, nb, d))
    r_spec = lambda d: pl.BlockSpec((1, 1, w), _r_at(nq, nb, d))
    # a sub-tile's own rows: the whole-window maps with sub-tiles as blocks
    q_tile = pl.BlockSpec((1, rows, dh), _q_at(1, nb * nq, 0))
    r_tile = pl.BlockSpec((1, 1, rows), _r_at(1, nb * nq, 0))
    out_tile = pl.BlockSpec((1, rows, dh), lambda bh, t: (bh, t, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, w=w, causal=causal, scale=scale,
                          nb=nb, nq=nq),
        grid=(B * H, nb * nq),
        in_specs=[
            q_tile,
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            kv_spec(-1), kv_spec(0), kv_spec(+1),
            q_tile, r_tile, r_tile,
        ],
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, kf, kf, vf, vf, vf, dof, lse, dsum)

    deltas = (0, 1) if causal else (-1, 0, 1)
    kv_tile = pl.BlockSpec((1, rows, dh), _kv_at(H, Hkv, 1, nb * nq, 0))
    dkv_in = ([kv_tile, kv_tile]
              + [q_spec(d) for d in deltas]
              + [q_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas])
    dkv_ops = ([kf, vf] + [qf] * len(deltas) + [dof] * len(deltas)
               + [lse] * len(deltas) + [dsum] * len(deltas))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, w=w, causal=causal, scale=scale,
                          nb=nb, nq=nq, deltas=deltas),
        grid=(B * H, nb * nq),
        in_specs=dkv_in,
        out_specs=[out_tile, out_tile],
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
