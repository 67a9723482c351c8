"""Routed (intra-cluster) attention — the Pallas TPU kernel. THE paper
hot-spot.

``routed_attention_fused`` implements stage 2 of the TPU adaptation
(DESIGN.md §3, §9) without gathering: q/k/v stay in sequence layout
(B,H,N,dh). The grid is (B·H, k), one step a cluster. A step pulls its
cluster's w member rows of q and v (and of k when keys are not shared)
with one-row ``make_async_copy`` DMAs into (w, dh) VMEM buffers, once
each — the page-table trick TPU paged attention uses, at row
granularity. All of a buffer's copies signal one DMA semaphore, and one
wait whose descriptor covers the whole buffer retires them (a DMA
semaphore counts bytes). Each buffer has two slots: cluster c+1's copies
are issued before cluster c's are waited on, so they overlap its compute;
the cluster axis is sequential ("arbitrary") and each batch·head starts
afresh. The cluster's (w,) membership indices, and the next cluster's,
arrive as blocked SMEM inputs (1 KiB each at w=256, whatever B, H or N),
the member positions for the causal mask as lane-dense int32 VMEM blocks
pre-gathered in XLA (4 B/row). No gathered (B,H,k,w,dh) q/k/v tensor ever
reaches HBM. In shared-QK causal mode the kernels take the one (q) index
array and read keys from the q buffer.

The compute loops over the held cluster's (bq, bk) sub-tiles, query
sub-tile by query sub-tile, key sub-tiles in ascending order, with
flash-style online softmax and float32 matmuls. In causal mode it skips
the pairs past the causal band when none of them holds an attendable
(query, key) pair: query sub-tile iq then visits key sub-tiles
[0, ``band_tiles``[iq]) alone, the T(T+1)/2 of T² pairs that sorted
members at increasing positions can need. Whether a cluster takes the
band is read from its members' positions (``band_clusters``, in XLA
beside the position gathers: each query sub-tile's latest position
against each later key sub-tile's earliest; padded keys sit at
SENTINEL), so a cluster whose members repeat across a sub-tile edge or
whose positions fall visits every pair, as does non-causal mode. A
skipped pair is one the mask covers entirely: it would add p = 0 with
corr = 1 to (m, l, acc), and ds = 0 to dq, dk and dv, so skipping it
changes no sum, and the band and every-pair sides agree bit for bit. The
band is a static loop bound under one branch a cluster, not a branch a
pair: on a v5e, a branch a pair with the softmax state in VMEM scratch
made the forward slower than visiting every pair (PERF.md §6). A
cluster's buffers and blocks are O(w·dh); a w whose cluster does not fit
``FUSED_CLUSTER_BYTES`` (kernels/common.py) is refused at trace time.

The kernel has two memory plans that differ only in where the row DMAs
read from, chosen from N·dh by ``fused_paged_default`` (kernels/
common.py):

* *resident* — the (N, dh) sequence plane of the current batch·head is
  the kernel's input block: one bulk DMA per plane, row DMAs VMEM->VMEM.
* *paged* — q/k/v stay in HBM (``memory_space=ANY``) and the row DMAs
  read HBM directly, so VMEM live bytes are O(w·dh) — independent of N.

Same kernels, same sub-tiles, same arithmetic: the two plans' forward
outputs are bit-identical. Single-row DMAs need 32-bit rows (Mosaic tiles
16-bit dtypes two rows per sublane), so 16-bit planes are widened to
float32 in XLA before the kernel; the kernel computes in float32 either
way, its matmuls at full float32 precision (``_dot``).

The kernel is differentiable (``jax.custom_vjp``): the forward emits
per-row lse stats (m + log l); the backward recomputes p = exp(s - lse)
tile by tile — no (w x w) matrix is ever stored. The backward is one
kernel on the forward's per-cluster grid and fetch plan: each p/ds
sub-tile feeds dq, dk and dv, and the kernel writes per-cluster gradient
blocks that XLA scatter-adds to sequence layout (duplicate memberships
accumulate, exactly the transpose of the implicit gather).

Row stats (lse, dsum) and positions travel as (..., 1, w) arrays so that
their blocks' last two dims tile on the chip. MXU-aligned: bq = bk = 128
default. The planes and member rows are ``row_lanes(dh)`` wide (a one-row
DMA moves whole 128-lane rows, so dh = 64 rows are zero-padded to 128 in
XLA); each loaded tile is cut back to dh, and its outputs are dh wide.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import (FUSED_CLUSTER_BYTES, default_interpret,
                                  float0_like, fused_cluster_bytes,
                                  fused_paged_default, fused_vmem_limit,
                                  row_lanes)
from repro.obs.trace import span

SENTINEL = 2 ** 30          # python int: usable inside the kernel body


def _dot(a, b, ca, cb):
    """Contract dim ``ca`` of ``a`` with dim ``cb`` of ``b`` in full
    float32. Mosaic's default rounds MXU operands to bfloat16; shared-QK
    routing softmax is saturated (each row's self score is sqrt(dh)), so
    the backward's ds = p·(dp - dsum) is a difference of near-equal terms,
    and a bfloat16-rounded dp against the float32 dsum buries dq/dk in
    rounding noise."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def band_tiles(w, bq, bk):
    """The causal band: query sub-tile iq's keys that sorted members at
    increasing positions can attend lie in key sub-tiles [0, hi[iq]),
    those that start at or before the query sub-tile's last row."""
    return [((iq + 1) * bq - 1) // bk + 1 for iq in range(w // bq)]


def band_clusters(pq, pk, causal, bq, bk):
    """Per cluster of (..., w) member positions (padded keys at
    SENTINEL): whether no sub-tile pair past the causal band holds a
    (query, key) pair ``_keep_mask`` keeps — each query sub-tile's
    latest position comes before every later key sub-tile's earliest.
    Always False in non-causal mode, where every pair is visited."""
    lead, w = pq.shape[:-1], pq.shape[-1]
    if not causal:
        return jnp.zeros(lead, bool)
    q_last = pq.reshape(*lead, w // bq, bq).max(-1)
    k_first = pk.reshape(*lead, w // bk, bk).min(-1)
    past = (np.arange(w // bk)[None, :]
            >= np.asarray(band_tiles(w, bq, bk))[:, None])
    live = q_last[..., :, None] >= k_first[..., None, :]
    return ~jnp.any(live & past, axis=(-2, -1))


def _keep_mask(pq, pk, causal):
    """Attendable (q row, k row) pairs. Padded keys carry pos = SENTINEL,
    which the causal comparison masks for free; the non-causal branch
    checks the sentinel explicitly."""
    if causal:
        return pq[:, None] >= pk[None, :]
    return jnp.broadcast_to((pk < SENTINEL)[None, :],
                            (pq.shape[0], pk.shape[0]))


# ---------------------------------------------------------------------------
# Fused gather-free kernel: sequence-layout q/k/v, one grid step a cluster,
# its member rows fetched once by one-row DMAs into double-buffered VMEM
# ---------------------------------------------------------------------------
def _start_rows(src, plane, idx_ref, dst, sem):
    """Issue one-row async copies ``src[plane, idx[j]] -> dst[j]`` for
    every row j of ``dst``, all signalling ``sem``. Cluster membership has
    no sequence locality, so rows — not contiguous chunks — are the DMA
    unit; the cluster's SMEM index block drives the source addresses (the
    same trick the paged decode kernel uses). Mosaic lowers a loop only
    whole or rolled, so the body issues ``unroll`` copies by hand."""
    w = dst.shape[0]
    unroll = math.gcd(w, 8)

    def body(i, _):
        for u in range(unroll):
            j = i * unroll + u
            row = idx_ref[0, 0, 0, j]
            pltpu.make_async_copy(src.at[plane, pl.ds(row, 1)],
                                  dst.at[pl.ds(j, 1)], sem).start()
        return 0
    jax.lax.fori_loop(0, w // unroll, body, 0)


def _wait_rows(dst, sem):
    """Retire every row copy into ``dst`` with one wait: a DMA semaphore
    counts bytes, and this descriptor covers the whole buffer."""
    pltpu.make_async_copy(dst, dst, sem).wait()


def _split_refs(refs, shared, n_in, n_out):
    """A fused kernel's refs: membership index blocks (current and next
    cluster, for q and, with separate keys, for k), member positions and
    the cluster's band flag, the (q, [k,] v) sources, ``n_in`` more
    inputs, ``n_out`` outputs, then one (2, w, dh) row buffer and one
    semaphore pair a plane."""
    planes = 2 if shared else 3
    sizes = (planes - 1) * 2, 3, planes, n_in, n_out, planes, planes
    parts, at = [], 0
    for n in sizes:
        parts.append(refs[at:at + n])
        at += n
    assert at == len(refs), (at, len(refs))
    return parts


def _cluster_rows(idx, srcs, bufs, sems, shared, resident):
    """Fetch the member rows of this grid step's cluster, and prefetch the
    next one's. Slot c % 2 holds cluster c; the next cluster's copies are
    issued before this one's are waited on, so they overlap its compute.
    Each batch·head starts afresh at c = 0 (the resident plan's plane
    block changes there). Returns the (q, k, v) row buffers of the slot;
    shared-QK keys are the q buffer, and v follows the key index."""
    qi, qn = idx[:2]
    ki, kn = (qi, qn) if shared else idx[2:]
    cur = (qi,) + (() if shared else (ki,)) + (ki,)
    nxt = (qn,) + (() if shared else (kn,)) + (kn,)
    plane = 0 if resident else pl.program_id(0)
    c = pl.program_id(1)
    slot = c % 2

    def start(index, s):
        for i_ref, src, buf, sem in zip(index, srcs, bufs, sems):
            _start_rows(src, plane, i_ref, buf.at[s], sem.at[s])

    @pl.when(c == 0)
    def _first():
        start(cur, 0)

    @pl.when(c + 1 < pl.num_programs(1))
    def _prefetch():
        start(nxt, 1 - slot)

    for buf, sem in zip(bufs, sems):
        _wait_rows(buf.at[slot], sem.at[slot])
    qb, vb = bufs[0].at[slot], bufs[-1].at[slot]
    return qb, (qb if shared else bufs[1].at[slot]), vb


def _rows(buf, r, dh):
    """Rows ``r`` of a member-row buffer, cut to the head dim (a dh = 64
    buffer row is 128 lanes wide, its last 64 zero)."""
    x = buf[r, :]
    return x if x.shape[-1] == dh else x[:, :dh]


def _by_band(band_ref, causal, w, bq, bk, body):
    """Run ``body(hi)``, where query sub-tile iq visits key sub-tiles
    [0, hi[iq]): the causal band when the cluster's flag says no pair
    past it is attendable, else every pair. One branch a cluster; each
    side's loops are unrolled whole."""
    every = [w // bk] * (w // bq)
    band = band_tiles(w, bq, bk)
    if not causal or band == every:
        body(every)
        return
    take = band_ref[0, 0, 0, 0] != 0

    @pl.when(take)
    def _band():
        body(band)

    @pl.when(jnp.logical_not(take))
    def _every():
        body(every)


def _fwd_kernel(*refs, shared, causal, scale, bq, bk, resident):
    idx, (pq_ref, pk_ref, band_ref), srcs, _, (o_ref, lse_ref), bufs, \
        sems = _split_refs(refs, shared, 0, 2)
    qb, kb, vb = _cluster_rows(idx, srcs, bufs, sems, shared, resident)
    w, dh = o_ref.shape[-2:]

    # online softmax over the held cluster's (iq, ik) sub-tiles, key
    # sub-tiles in ascending order; a pair past hi[iq] would leave
    # (m, l, acc) as they are
    def attend(hi):
        for iq in range(w // bq):
            rq = pl.ds(iq * bq, bq)
            q = _rows(qb, rq, dh)
            pq = pq_ref[0, 0, 0, rq]
            m = jnp.full((bq,), _NEG, jnp.float32)
            l = jnp.zeros((bq,), jnp.float32)
            acc = jnp.zeros((bq, dh), jnp.float32)
            for ik in range(hi[iq]):
                rk = pl.ds(ik * bk, bk)
                s = _dot(q, _rows(kb, rk, dh), 1, 1) * scale
                keep = _keep_mask(pq, pk_ref[0, 0, 0, rk], causal)
                s = jnp.where(keep, s, _NEG)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(-1)
                acc = acc * corr[:, None] + _dot(p, _rows(vb, rk, dh), 1, 0)
                m = m_new
            l = jnp.maximum(l, 1e-30)
            o_ref[0, 0, rq, :] = (acc / l[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0, 0, rq] = m + jnp.log(l)

    _by_band(band_ref, causal, w, bq, bk, attend)


def _bwd_kernel(*refs, shared, causal, scale, bq, bk, resident):
    (idx, (pq_ref, pk_ref, band_ref), srcs, (do_ref, lse_ref, dsum_ref),
     (dq_ref, dk_ref, dv_ref), bufs, sems) = _split_refs(refs, shared, 3, 3)
    qb, kb, vb = _cluster_rows(idx, srcs, bufs, sems, shared, resident)
    w, dh = dq_ref.shape[-2:]

    # one p/ds sub-tile feeds dq, dk and dv; dq sums over key sub-tiles
    # and dk/dv over query sub-tiles, each in ascending order. A pair past
    # hi[iq] has p = ds = 0; every key sub-tile is in the last query
    # sub-tile's range, so each dk/dv block is written
    def grads(hi):
        written = set()
        for iq in range(w // bq):
            rq = pl.ds(iq * bq, bq)
            q = _rows(qb, rq, dh)
            do = do_ref[0, 0, rq, :].astype(jnp.float32)
            pq = pq_ref[0, 0, 0, rq]
            lse = lse_ref[0, 0, 0, rq]
            dsum = dsum_ref[0, 0, 0, rq]
            dq = None
            for ik in range(hi[iq]):
                rk = pl.ds(ik * bk, bk)
                k = _rows(kb, rk, dh)
                keep = _keep_mask(pq, pk_ref[0, 0, 0, rk], causal)
                s = _dot(q, k, 1, 1) * scale
                p = jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
                dp = _dot(do, _rows(vb, rk, dh), 1, 1)
                ds = p * (dp - dsum[:, None]) * scale
                dq_t = _dot(ds, k, 1, 0)
                dq = dq_t if dq is None else dq + dq_t
                dv_t = _dot(p, do, 0, 0)
                dk_t = _dot(ds, q, 0, 0)
                if ik in written:
                    dv_ref[0, 0, rk, :] += dv_t
                    dk_ref[0, 0, rk, :] += dk_t
                else:
                    dv_ref[0, 0, rk, :] = dv_t
                    dk_ref[0, 0, rk, :] = dk_t
                    written.add(ik)
            dq_ref[0, 0, rq, :] = dq

    _by_band(band_ref, causal, w, bq, bk, grads)


def _fused_call(kernel, qf, kf, vf, qi, ki, pqg, pkg, band, extra, n_out,
                shared, causal, bq, bk, resident, interpret, dh,
                lse_out=False):
    """One fused kernel over the (B·H, k) grid, a cluster a step. Inputs:
    the membership index blocks of this cluster and the next (SMEM), the
    member positions, the cluster's band flag (SMEM), the q [k] v
    sources — (N, row_lanes(dh)) planes, the batch·head's whole plane
    when resident, untouched HBM (ANY) when paged — then ``extra``
    per-cluster (w, dh) and (1, w) blocks. Outputs: ``n_out`` float32
    (w, dh) blocks, then the (1, w) row stats if ``lse_out``."""
    BH, N, lanes = qf.shape
    _, kc, _, w = qi.shape
    planes = 2 if shared else 3
    f32 = jnp.float32
    cur = lambda b, c: (b, c, 0, 0)
    nxt = lambda b, c: (b, jnp.minimum(c + 1, kc - 1), 0, 0)
    idx = [pl.BlockSpec((1, 1, 1, w), at, memory_space=pltpu.SMEM)
           for at in (cur, nxt)]
    if resident:
        src = pl.BlockSpec((1, N, lanes), lambda b, c: (b, 0, 0))
    else:
        src = pl.BlockSpec(memory_space=pl.ANY)
    tile = pl.BlockSpec((1, 1, w, dh), cur)
    row = pl.BlockSpec((1, 1, 1, w), cur)
    flag = pl.BlockSpec((1, 1, 1, 1), cur, memory_space=pltpu.SMEM)
    indices = (qi, qi) + (() if shared else (ki, ki))
    srcs = (qf,) + (() if shared else (kf,)) + (vf,)
    out_shape = [jax.ShapeDtypeStruct((BH, kc, w, dh), f32)] * n_out
    out_specs = [tile] * n_out
    if lse_out:
        out_shape.append(jax.ShapeDtypeStruct((BH, kc, 1, w), f32))
        out_specs.append(row)
    return pl.pallas_call(
        functools.partial(kernel, shared=shared, causal=causal,
                          scale=1.0 / (dh ** 0.5), bq=bq, bk=bk,
                          resident=resident),
        grid=(BH, kc),
        in_specs=(idx * (len(indices) // 2) + [row, row, flag]
                  + [src] * planes
                  + [row if x.shape[-2] == 1 else tile for x in extra]),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM((2, w, lanes), f32)] * planes
                        + [pltpu.SemaphoreType.DMA((2,))] * planes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=fused_vmem_limit(N, dh, planes, resident)),
        interpret=interpret,
    )(*indices, pqg, pkg, band, *srcs, *extra)


def _fused_fwd_call(qf, kf, vf, qi, ki, pqg, pkg, band, shared, causal, bq,
                    bk, resident, interpret, dh):
    return _fused_call(_fwd_kernel, qf, kf, vf, qi, ki, pqg, pkg, band, (),
                       1, shared, causal, bq, bk, resident, interpret, dh,
                       lse_out=True)


def _fused_bwd_call(qf, kf, vf, qi, ki, pqg, pkg, band, out, lse, do,
                    shared, causal, bq, bk, resident, interpret):
    BH, N, _ = qf.shape
    dh = do.shape[-1]
    f32 = jnp.float32
    # the softmax backward's row sums, in XLA: the routing stage's
    # attention work, outside the kernel's span
    with span("routing/attend"):
        dsum = (do * out).sum(-1)[:, :, None, :]
    dqg, dkg, dvg = _fused_call(_bwd_kernel, qf, kf, vf, qi, ki, pqg, pkg,
                                band, (do, lse, dsum), 3, shared, causal, bq,
                                bk, resident, interpret, dh)

    # scatter-add per-cluster gradient blocks back to sequence layout —
    # the exact transpose of the kernel's implicit gather; duplicate
    # memberships accumulate. XLA work, so it takes the routing stage's
    # span rather than the kernel's
    with span("routing/scatter"):
        bi = jnp.arange(BH)[:, None]
        qi2 = qi.reshape(BH, -1)
        ki2 = ki.reshape(BH, -1)
        dq = jnp.zeros((BH, N, dh), f32).at[bi, qi2].add(
            dqg.reshape(BH, -1, dh))
        dk = jnp.zeros((BH, N, dh), f32).at[bi, ki2].add(
            dkg.reshape(BH, -1, dh))
        dv = jnp.zeros((BH, N, dh), f32).at[bi, ki2].add(
            dvg.reshape(BH, -1, dh))
    return dq, dk, dv


def _lane_planes(qf, kf, vf, shared):
    """The (q, k, v) planes at ``row_lanes(dh)``: a one-row DMA moves a
    whole 128-lane row, so narrower rows are zero-padded (in XLA, inside
    the kernel's span); shared-QK keys stay the q plane."""
    dh = qf.shape[-1]
    pad = row_lanes(dh) - dh
    if pad:
        qf, vf = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (qf, vf))
        kf = qf if shared else jnp.pad(kf, ((0, 0), (0, 0), (0, pad)))
    return qf, kf, vf


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _routed_fused(shared, causal, bq, bk, resident, interpret, qf, kf, vf,
                  qi, ki, pqg, pkg, band):
    out, _ = _fused_fwd_call(*_lane_planes(qf, kf, vf, shared), qi, ki, pqg,
                             pkg, band, shared, causal, bq, bk, resident,
                             interpret, qf.shape[-1])
    return out


def _routed_fused_fwd(shared, causal, bq, bk, resident, interpret, qf, kf,
                      vf, qi, ki, pqg, pkg, band):
    qp, kp, vp = _lane_planes(qf, kf, vf, shared)
    out, lse = _fused_fwd_call(qp, kp, vp, qi, ki, pqg, pkg, band, shared,
                               causal, bq, bk, resident, interpret,
                               qf.shape[-1])
    return out, (qp, kp, vp, qi, ki, pqg, pkg, band, out, lse)


def _routed_fused_bwd(shared, causal, bq, bk, resident, interpret, res, do):
    qf, kf, vf, qi, ki, pqg, pkg, band, out, lse = res
    dq, dk, dv = _fused_bwd_call(qf, kf, vf, qi, ki, pqg, pkg, band, out,
                                 lse, do, shared, causal, bq, bk, resident,
                                 interpret)
    return (dq, dk, dv, float0_like(qi), float0_like(ki),
            float0_like(pqg), float0_like(pkg), float0_like(band))


_routed_fused.defvjp(_routed_fused_fwd, _routed_fused_bwd)


def _member_positions(positions, kvalid, qi, ki):
    """The (B, N) positions of the (B·H, k, 1, w) query and key members,
    int32; keys that ``kvalid`` marks as padding read SENTINEL."""
    B, N = positions.shape
    BH, kc, _, w = qi.shape
    posq = positions.astype(jnp.int32)
    posk = (jnp.where(kvalid, posq, SENTINEL) if kvalid is not None
            else posq)

    def member_pos(pos, idx):
        src = jnp.broadcast_to(pos[:, None, :], (B, BH // B, N))
        return jnp.take_along_axis(src.reshape(BH, N),
                                   idx.reshape(BH, kc * w),
                                   axis=1).reshape(BH, kc, 1, w)

    return member_pos(posq, qi), member_pos(posk, ki)


def computed_tile_share(positions, q_idx, k_idx, causal, kvalid=None,
                        bq=128, bk=128):
    """(H,) share of the (bq, bk) sub-tile pairs that
    ``routed_attention_fused`` computes for these memberships, mean over
    batch and clusters: the band's where ``band_clusters`` holds, else 1.
    Arguments as there (k_idx None: shared keys)."""
    B, H, kc, w = q_idx.shape
    bq, bk = min(bq, w), min(bk, w)
    qi = q_idx.reshape(B * H, kc, 1, w).astype(jnp.int32)
    ki = qi if k_idx is None else k_idx.reshape(B * H, kc, 1, w).astype(
        jnp.int32)
    band = band_clusters(*_member_positions(positions, kvalid, qi, ki),
                         causal, bq, bk)
    share = sum(band_tiles(w, bq, bk)) / ((w // bq) * (w // bk))
    return jnp.where(band, share, 1.0).reshape(B, H, kc).mean((0, 2))


def routed_attention_fused(q, k, v, q_idx, k_idx, positions, causal=True,
                           kvalid=None, bq=128, bk=128, interpret=None,
                           paged=None):
    """Gather-free routed attention on sequence-layout tensors.

    q/v: (B,H,N,dh); k: like q, or None for shared-QK causal mode.
    q_idx/k_idx: (B,H,k,w) sorted membership indices into the sequence.
    In shared mode the keys are the queries: the kernels take q_idx
    alone and read keys from the q rows they fetched, so ``k_idx`` is
    not read (pass None, or q_idx).
    positions: (B,N) int32 original positions (the causal mask compares
    these). kvalid: (B,N) bool, True = attendable key (padding False).
    Returns per-cluster outputs (B,H,k,w,dh); callers scatter them back.

    ``paged=None`` (every caller in the program) chooses the memory
    plan from N·dh: whole-plane VMEM residency while the planes fit the
    byte budget of ``fused_paged_default``, row DMAs straight from HBM
    beyond it (VMEM bounded by the cluster size w, not N). Tests pass
    True/False to run one plan at any size. A cluster whose rows do not
    fit ``FUSED_CLUSTER_BYTES`` of VMEM is refused here (ValueError).
    Member positions are pre-gathered in XLA (int32, 4 B/row) — still no
    gathered q/k/v tensor in HBM.

    Differentiable: flash-style custom VJP that recomputes p from saved
    lse stats and scatter-adds per-cluster dq/dk/dv to sequence layout.
    """
    B, H, N, dh = q.shape
    _, _, kc, w = q_idx.shape
    bq = min(bq, w)
    bk = min(bk, w)
    assert w % bq == 0 and w % bk == 0, (w, bq, bk)
    shared = k is None
    planes = 2 if shared else 3
    need = fused_cluster_bytes(w, dh, planes)
    if need > FUSED_CLUSTER_BYTES:
        raise ValueError(
            f"fused routing kernel: a cluster of w={w} rows (dh={dh}, "
            f"{planes} planes) needs {need} B of VMEM, over "
            f"FUSED_CLUSTER_BYTES={FUSED_CLUSTER_BYTES}; use more clusters")
    f32 = jnp.float32
    qf = q.reshape(B * H, N, dh).astype(f32)
    kf = qf if shared else k.reshape(B * H, N, dh).astype(f32)
    vf = v.reshape(B * H, N, dh).astype(f32)
    qi = q_idx.reshape(B * H, kc, 1, w).astype(jnp.int32)
    ki = qi if shared else k_idx.reshape(B * H, kc, 1, w).astype(jnp.int32)
    resident = not fused_paged_default(N, dh, planes, paged)
    # XLA's gathers of the members' positions, and the clusters' band
    # flags read from them, take the routing stage's span, so the
    # kernel's span holds the kernel's own time
    with span("routing/gather"):
        pqg, pkg = _member_positions(positions, kvalid, qi, ki)
        band = band_clusters(pqg, pkg, causal, bq, bk).astype(
            jnp.int32)[..., None]
    out = _routed_fused(shared, bool(causal), int(bq), int(bk), resident,
                        default_interpret(interpret), qf, kf, vf, qi, ki,
                        pqg, pkg, band)
    return out.reshape(B, H, kc, w, dh).astype(q.dtype)
