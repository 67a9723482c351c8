"""Blocked local (sliding-window) attention — Pallas TPU kernel,
differentiable.

The paper's local attention: each block of w queries attends its own
block and the previous one (+ next in encoder mode). The KV tiles are
index-mapped views of the same HBM array (block b-1 clamps to 0 and is
masked for b == 0).

Grid: (batch·head, window block × its query sub-tiles). A query sub-tile
has ``sub_tile(w, dh)`` rows. For w <= 512 that is the whole window: one
grid step a block, whose softmax over the concatenated 2w (3w) keys
happens in VMEM in one shot (a (w x 2w) fp32 score tile of ~2 MiB).
Past it (w = 2048: 128 rows) the window is sub-tiled, and query sub-tile
r of block b scores only the key tiles the causal mask leaves, in at
most three chunks, each in one shot: the previous block unless b == 0,
its own block's r tiles before the diagonal, then the diagonal tile,
the only one masked (encoder mode: the whole own block, and the
previous and next unless they are clamped). A running (online) softmax
carries its max, sum and output between the chunks as values; each
sub-tile r has a branch of its own with static slices.
``live_tile_share`` counts what this skips. The key/value blocks' index
does not change across a block's sub-tiles, so they are fetched once a
block and chunks are sliced out of VMEM.

Backward (``jax.custom_vjp``): the forward also emits per-row lse stats;
the dq kernel mirrors the forward (recompute p = exp(s - lse),
dq = ds @ K). The dk/dv kernel inverts the window: a key sub-tile of
block b is attended by query blocks {b, b+1} (causal; {b-1, b, b+1} in
encoder mode), so it index-maps those whole q/do/lse/D blocks in
(clamped at the edges). The whole-window grid masks them via intended
positions; a sub-tiled key sub-tile r takes its own block's diagonal
query tile (masked) and the tiles after it, and the next block unless b
is the last.
dk/dv come out per *query* head and are group-summed to the GQA kv
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

# float32 bytes a sub-tile's score rows (and its q row) may take
_SUB_TILE_BYTES = 4 << 20
# scoped VMEM of a sub-tiled window's kernels: the whole (w, dh) blocks,
# double-buffered at 128 lanes, take 8 MiB at w = 2048 (12 MiB in
# encoder mode) beside (rows, w) float32 score chunks of 1 MiB, past
# v5e's default limit of 16 MiB
_TILED_VMEM_BYTES = 32 << 20


def _tiles(nq):
    """The window block of grid step ``t``: its ``nq`` sub-tiles are
    consecutive steps, and sub-tile rows start at ``t * rows``."""
    return (lambda t: t) if nq == 1 else (lambda t: t // nq)


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
    """Key block b gathers contributions from the whole q blocks that
    attend it (b + delta for delta in ``deltas``); edge blocks are
    clamped by the index map and neutralized by the intended-position
    mask."""
    b = pl.program_id(1)
    q_refs, do_refs, lse_refs, dsum_refs = _by_delta(refs, len(deltas))
    dk_ref, dv_ref = refs[4 * len(deltas):]
    k = k_ref[0].astype(jnp.float32)                    # (w, dh)
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


def _by_delta(refs, n):
    """The q, do, lse and D refs of the ``n`` attending query blocks."""
    return refs[0:n], refs[n:2 * n], refs[2 * n:3 * n], refs[3 * n:4 * n]


def _nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())))


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))


def _rows(ref, start, size):
    """Rows [start, start + size) of a (1, w, dh) block, in float32."""
    return ref[0, start:start + size, :].astype(jnp.float32)


def _diagonal(rows):
    """Row and column indices of the (rows, rows) score tile whose query
    and key rows start at the same position: the one tile the causal
    mask cuts."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0),
            jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1))


def _unless_edge(live, fn, carry):
    """``fn(carry)`` where the block it reads exists, else ``carry``: the
    index map clamps the previous block of b == 0 and the next block of
    the last, and they take no work."""
    return jax.lax.cond(live, fn, lambda c: c, carry)


def _by_sub_tile(r, nq, fn):
    """``fn(rr)`` under the branch of sub-tile ``r == rr``: each branch
    reads static slices of its own block."""
    for rr in range(nq):
        pl.when(r == rr)(functools.partial(fn, rr))


def _tiled_kernel(q_ref, *refs, causal, scale, nb, nq):
    """Query sub-tile r of block b, its q pre-scaled: a softmax over the
    key chunks the mask leaves, each chunk in one shot and the running
    max, sum and output carried between them as values. Chunks: the
    previous block unless b == 0, the own block's tiles before the
    diagonal, then the diagonal tile, the only one masked (encoder mode:
    the whole own block and the next unless b is the last)."""
    n = len(refs) // 2 - 1                  # key blocks: prev, own (, next)
    k_refs, v_refs, (o_ref, lse_ref) = refs[:n], refs[n:2 * n], refs[2 * n:]
    t = pl.program_id(1)
    b, r = t // nq, t % nq
    q = q_ref[0].astype(jnp.float32) * scale            # (rows, dh)
    rows, dh = q.shape
    w = k_refs[0].shape[1]

    def attend(i, start=0, size=w, keep=None):
        def chunk(carry):
            m, l, acc = carry
            s = _nt(q, _rows(k_refs[i], start, size))
            if keep is not None:
                # every row keeps its diagonal key, so the masked scores'
                # exp underflows to 0 against the row max
                s = jnp.where(keep, s, _NEG)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            acc = acc * corr + _nn(p, _rows(v_refs[i], start, size))
            return m_new, l * corr + p.sum(-1, keepdims=True), acc
        return chunk

    def finish(carry):
        m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]

    carry = (jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, dh), jnp.float32))
    carry = _unless_edge(b > 0, attend(0), carry)
    if causal:
        def own(rr):                        # query rr·rows + i, key rr·rows + c
            i, c = _diagonal(rows)
            before = attend(1, 0, rr * rows)(carry) if rr else carry
            finish(attend(1, rr * rows, rows, c <= i)(before))
        _by_sub_tile(r, nq, own)
    else:
        carry = attend(1)(carry)
        finish(_unless_edge(b < nb - 1, attend(2), carry))


def _tiled_dq_kernel(q_ref, *refs, causal, scale, nb, nq):
    """dq of query sub-tile r of block b (q pre-scaled), summed over the
    forward's key chunks."""
    n = (len(refs) - 4) // 2
    k_refs, v_refs = refs[:n], refs[n:2 * n]
    do_ref, lse_ref, dsum_ref, dq_ref = refs[2 * n:]
    t = pl.program_id(1)
    b, r = t // nq, t % nq
    q = q_ref[0].astype(jnp.float32) * scale
    rows = q.shape[0]
    w = k_refs[0].shape[1]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    dsum = dsum_ref[0, 0][:, None]

    def grad(i, start=0, size=w, keep=None):
        def chunk(dq):
            k = _rows(k_refs[i], start, size)
            p = jnp.exp(_nt(q, k) - lse)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
            dp = _nt(do, _rows(v_refs[i], start, size))
            return dq + _nn(p * (dp - dsum), k)
        return chunk

    dq = _unless_edge(b > 0, grad(0), jnp.zeros_like(q))
    if causal:
        def own(rr):
            i, c = _diagonal(rows)
            before = grad(1, 0, rr * rows)(dq) if rr else dq
            dq_ref[0] = grad(1, rr * rows, rows, c <= i)(before) * scale
        _by_sub_tile(r, nq, own)
    else:
        dq = grad(1)(dq)
        dq_ref[0] = _unless_edge(b < nb - 1, grad(2), dq) * scale


def _tiled_dkv_kernel(k_ref, v_ref, *refs, causal, scale, nb, nq):
    """Key sub-tile r of block b (k pre-scaled): dk and dv summed over the
    chunks of the query blocks that attend it — the next block unless b
    is the last, its own block's diagonal tile, masked, and the own
    block's tiles after it (encoder mode: the whole own block and the
    previous and next unless they are clamped) — with keys along the
    score chunk's rows, so the row stats broadcast along lanes."""
    n = 2 if causal else 3                  # query blocks: (prev,) own, next
    q_refs, do_refs, lse_refs, dsum_refs = _by_delta(refs, n)
    dk_ref, dv_ref = refs[4 * n:]
    t = pl.program_id(1)
    b, r = t // nq, t % nq
    k = k_ref[0].astype(jnp.float32) * scale            # (rows, dh)
    v = v_ref[0].astype(jnp.float32)
    rows = k.shape[0]
    w = q_refs[0].shape[1]

    def grad(i, start=0, size=w, keep=None):
        def chunk(carry):
            dk, dv = carry
            q = _rows(q_refs[i], start, size)
            do = _rows(do_refs[i], start, size)
            lse = lse_refs[i][0, :, start:start + size]  # (1, size)
            dsum = dsum_refs[i][0, :, start:start + size]
            p = jnp.exp(_nt(k, q) - lse)                # (keys, queries)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
            ds = p * (_nt(v, do) - dsum)
            return dk + _nn(ds, q), dv + _nn(p, do)
        return chunk

    def finish(carry):
        dk, dv = carry
        dk_ref[0] = dk * scale
        dv_ref[0] = dv

    carry = (jnp.zeros_like(k), jnp.zeros_like(v))
    if causal:
        carry = _unless_edge(b < nb - 1, grad(1), carry)

        def own(rr):                        # key rr·rows + i, query rr·rows + c
            i, c = _diagonal(rows)
            carry_d = grad(0, rr * rows, rows, c >= i)(carry)
            after = (rr + 1) * rows
            finish(grad(0, after, w - after)(carry_d) if after < w
                   else carry_d)
        _by_sub_tile(r, nq, own)
    else:
        carry = _unless_edge(b > 0, grad(0), carry)
        carry = grad(1)(carry)
        finish(_unless_edge(b < nb - 1, grad(2), carry))


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


def live_tile_share(n: int, w: int, dh: int, causal: bool = True) -> float:
    """Share of the whole-window kernels' (rows x rows) score tiles that
    the kernels compute at sequence ``n``: 1.0 where the window is one
    sub-tile (that grid is the whole-window one); else the sub-tiled
    grid's live tiles — query sub-tile r of block b meets r + 1 tiles of
    its own block and all of the previous one unless b == 0 (encoder
    mode: the three blocks, less the clamped edges)."""
    w = min(w, n)
    nb, nq = n // w, w // sub_tile(w, dh)
    if nq == 1:
        return 1.0
    if causal:
        return ((2 * (nb - 1) * nq * nq + nb * nq * (nq + 1))
                / (4 * nb * nq * nq))
    return (3 * nb - 2) / (3 * nb)


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


def _deltas(causal):
    """Key blocks a query block attends, relative to its own."""
    return (-1, 0) if causal else (-1, 0, 1)


def _fwd_call(q, k, v, w, causal, interpret):
    B, H, Hkv, N, dh = _shapes(q, k)
    nb = N // w
    bq = sub_tile(w, dh)
    nq = w // bq
    qf = q.reshape(B * H, N, dh)
    kf = k.reshape(B * Hkv, N, dh)
    vf = v.reshape(B * Hkv, N, dh)
    scale = 1.0 / (dh ** 0.5)
    kv_spec = lambda d: pl.BlockSpec((1, w, dh), _kv_at(H, Hkv, nq, nb, d))
    if nq == 1:
        kernel = functools.partial(_kernel, w=w, causal=causal, scale=scale,
                                   nb=nb)
        ds = (-1, 0, 1)
    else:
        kernel = functools.partial(_tiled_kernel, causal=causal, scale=scale,
                                   nb=nb, nq=nq)
        ds = _deltas(causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nb * nq),
        in_specs=([pl.BlockSpec((1, bq, dh), lambda bh, t: (bh, t, 0))]
                  + [kv_spec(d) for d in ds] * 2),
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
    )(qf, *[kf] * len(ds), *[vf] * len(ds))
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
    # a sub-tile's own rows: the whole-window maps with sub-tiles as blocks
    q_tile = pl.BlockSpec((1, rows, dh), _q_at(1, nb * nq, 0))
    r_tile = pl.BlockSpec((1, 1, rows), _r_at(1, nb * nq, 0))
    out_tile = pl.BlockSpec((1, rows, dh), lambda bh, t: (bh, t, 0))
    if nq == 1:
        dq_kernel = functools.partial(_bwd_dq_kernel, w=w, causal=causal,
                                      scale=scale, nb=nb)
        ds = (-1, 0, 1)
    else:
        dq_kernel = functools.partial(_tiled_dq_kernel, causal=causal,
                                      scale=scale, nb=nb, nq=nq)
        ds = _deltas(causal)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, nb * nq),
        in_specs=([q_tile] + [kv_spec(d) for d in ds] * 2
                  + [q_tile, r_tile, r_tile]),
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(qf, *[kf] * len(ds), *[vf] * len(ds), dof, lse, dsum)

    deltas = (0, 1) if causal else (-1, 0, 1)
    r_spec = lambda d: pl.BlockSpec((1, 1, w), _r_at(nq, nb, d))
    if nq == 1:
        dkv_kernel = functools.partial(_bwd_dkv_kernel, w=w, causal=causal,
                                       scale=scale, nb=nb, deltas=deltas)
    else:
        dkv_kernel = functools.partial(_tiled_dkv_kernel, causal=causal,
                                       scale=scale, nb=nb, nq=nq)
    kv_tile = pl.BlockSpec((1, rows, dh), _kv_at(H, Hkv, 1, nb * nq, 0))
    dkv_in = ([kv_tile, kv_tile]
              + [q_spec(d) for d in deltas]
              + [q_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas]
              + [r_spec(d) for d in deltas])
    dkv_ops = ([kf, vf] + [qf] * len(deltas) + [dof] * len(deltas)
               + [lse] * len(deltas) + [dsum] * len(deltas))
    dk, dv = pl.pallas_call(
        dkv_kernel,
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
