"""Routed (intra-cluster) attention — Pallas TPU kernels. THE paper hot-spot.

Two kernels implement stage 2 of the TPU adaptation (DESIGN.md §3, §9):

``routed_attention_blocks`` — the original *gathered* kernel: XLA
materializes (B,H,k,w,dh) copies of q/k/v (three HBM round-trips of the
whole sequence, four in shared-QK mode before the dedupe) and the kernel
streams the cluster blocks with flash-style online softmax.

``routed_attention_fused`` — the *gather-free* kernel: q/k/v stay in
sequence layout (B,H,N,dh) and every grid step pulls exactly the bq/bk
member rows of its cluster tile with per-row ``make_async_copy`` DMAs into
revolving double-buffered VMEM slots (tile ik+1's DMAs issue before tile
ik's compute runs) — the page-table trick TPU paged attention uses, at row
granularity. The cluster's (w,) membership indices arrive per grid step as
a blocked SMEM input (1 KiB at w=256, whatever B, H or N), and the member
positions for the causal mask as lane-dense int32 VMEM blocks pre-gathered
in XLA (4 B/row). No gathered (B,H,k,w,dh) q/k/v tensor ever reaches HBM,
and shared-QK causal mode reads keys from the q plane.

The fused kernel has two memory plans that differ only in where the row
DMAs read from (``paged=None`` auto-switches on the byte budget in
kernels/common.py):

* *resident* — the (N, dh) sequence plane of the current batch·head is
  the kernel's input block: one bulk DMA per plane, row DMAs VMEM->VMEM.
* *paged* — q/k/v stay in HBM (``memory_space=ANY``) and the row DMAs
  read HBM directly, so VMEM live bytes are O(bq·dh + 4·bk·dh) —
  independent of N.

Same kernels, same tiles, same arithmetic: the two plans' forward outputs
are bit-identical. Single-row DMAs need 32-bit rows (Mosaic tiles 16-bit
dtypes two rows per sublane), so 16-bit planes are widened to float32 in
XLA before the kernel; the kernel computes in float32 either way, its
matmuls at full float32 precision (``_dot``).

Both kernels are differentiable (``jax.custom_vjp``): the forward emits
per-row lse stats (m + log l); the backward recomputes p = exp(s - lse)
tile by tile — no (w x w) matrix is ever stored — and runs a dq kernel
(KV-sequential grid) plus a dk/dv kernel (Q-sequential grid) over the same
cluster-block structure. The fused backward produces per-cluster gradient
blocks and scatter-adds them to sequence layout in XLA (duplicate
memberships accumulate, exactly the transpose of the implicit gather).

Row stats (lse, dsum) and positions travel as (..., 1, w) arrays so that
their blocks' last two dims, (1, b), tile on the chip.

Grid: (B·H·k clusters, w/bq, w/bk) gathered; (B·H, k, w/bq, w/bk) fused,
KV axis sequential; (m, l, acc) scratch in VMEM. MXU-aligned: bq = bk =
128 default, dh in {64, 128, 256}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import (default_interpret, float0_like,
                                  fused_paged_default, fused_vmem_limit)
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


def _keep_mask(pq, pk, causal):
    """Attendable (q row, k row) pairs. Padded keys carry pos = SENTINEL,
    which the causal comparison masks for free; the non-causal branch
    checks the sentinel explicitly."""
    if causal:
        return pq[:, None] >= pk[None, :]
    return jnp.broadcast_to((pk < SENTINEL)[None, :],
                            (pq.shape[0], pk.shape[0]))


# ---------------------------------------------------------------------------
# Gathered kernel (blocks already materialized by XLA)
# ---------------------------------------------------------------------------
def _kernel(q_ref, k_ref, v_ref, pq_ref, pk_ref, o_ref, lse_ref,
            m_ref, l_ref, acc_ref, *, causal, scale):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    pq = pq_ref[0, 0]                                 # (bq,) int32
    pk = pk_ref[0, 0]                                 # (bk,) int32
    s = _dot(q, k, 1, 1) * scale
    keep = _keep_mask(pq, pk, causal)
    s = jnp.where(keep, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + \
        _dot(p, v, 1, 0)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _g_dq_kernel(q_ref, k_ref, v_ref, pq_ref, pk_ref, do_ref, lse_ref,
                 dsum_ref, dq_ref, dq_acc, *, causal, scale):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    keep = _keep_mask(pq_ref[0, 0], pk_ref[0, 0], causal)
    s = _dot(q, k, 1, 1) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
    dp = _dot(do, v, 1, 1)
    ds = p * (dp - dsum_ref[0, 0][:, None]) * scale
    dq_acc[...] += _dot(ds, k, 1, 0)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = dq_acc[...]


def _g_dkv_kernel(q_ref, k_ref, v_ref, pq_ref, pk_ref, do_ref, lse_ref,
                  dsum_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                  scale):
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    keep = _keep_mask(pq_ref[0, 0], pk_ref[0, 0], causal)
    s = _dot(q, k, 1, 1) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
    dv_acc[...] += _dot(p, do, 0, 0)
    dp = _dot(do, v, 1, 1)
    ds = p * (dp - dsum_ref[0, 0][:, None]) * scale
    dk_acc[...] += _dot(ds, q, 0, 0)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def _g_fwd_call(qf, kf, vf, pqf, pkf, causal, bq, bk, interpret):
    n, w, dh = qf.shape
    grid = (n, w // bq, w // bk)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=1.0 / (dh ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda c, iq, ik: (c, iq, 0)),
            pl.BlockSpec((1, bk, dh), lambda c, iq, ik: (c, ik, 0)),
            pl.BlockSpec((1, bk, dh), lambda c, iq, ik: (c, ik, 0)),
            pl.BlockSpec((1, 1, bq), lambda c, iq, ik: (c, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda c, iq, ik: (c, 0, ik)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda c, iq, ik: (c, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda c, iq, ik: (c, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, w, dh), qf.dtype),
            jax.ShapeDtypeStruct((n, 1, w), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, pqf, pkf)
    return out, lse


def _g_bwd_call(qf, kf, vf, pqf, pkf, out, lse, do, causal, bq, bk,
                interpret):
    n, w, dh = qf.shape
    dsum = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dsum = dsum.reshape(n, 1, w)
    scale = 1.0 / (dh ** 0.5)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    q_at = lambda c, iq, ik: (c, iq, 0)
    k_at = lambda c, iq, ik: (c, ik, 0)
    rq_at = lambda c, iq, ik: (c, 0, iq)
    rk_at = lambda c, iq, ik: (c, 0, ik)
    dq = pl.pallas_call(
        functools.partial(_g_dq_kernel, causal=causal, scale=scale),
        grid=(n, w // bq, w // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_at),
            pl.BlockSpec((1, bk, dh), k_at),
            pl.BlockSpec((1, bk, dh), k_at),
            pl.BlockSpec((1, 1, bq), rq_at),
            pl.BlockSpec((1, 1, bk), rk_at),
            pl.BlockSpec((1, bq, dh), q_at),
            pl.BlockSpec((1, 1, bq), rq_at),
            pl.BlockSpec((1, 1, bq), rq_at),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), q_at),
        out_shape=jax.ShapeDtypeStruct((n, w, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, pqf, pkf, do, lse, dsum)

    # swapped grid: key tile parallel, query sweep sequential
    q_at2 = lambda c, ik, iq: (c, iq, 0)
    k_at2 = lambda c, ik, iq: (c, ik, 0)
    rq_at2 = lambda c, ik, iq: (c, 0, iq)
    rk_at2 = lambda c, ik, iq: (c, 0, ik)
    dk, dv = pl.pallas_call(
        functools.partial(_g_dkv_kernel, causal=causal, scale=scale),
        grid=(n, w // bk, w // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_at2),
            pl.BlockSpec((1, bk, dh), k_at2),
            pl.BlockSpec((1, bk, dh), k_at2),
            pl.BlockSpec((1, 1, bq), rq_at2),
            pl.BlockSpec((1, 1, bk), rk_at2),
            pl.BlockSpec((1, bq, dh), q_at2),
            pl.BlockSpec((1, 1, bq), rq_at2),
            pl.BlockSpec((1, 1, bq), rq_at2),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dh), k_at2),
            pl.BlockSpec((1, bk, dh), k_at2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, w, dh), jnp.float32),
            jax.ShapeDtypeStruct((n, w, dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dh), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, pqf, pkf, do, lse, dsum)
    return (dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _routed_gathered(causal, bq, bk, interpret, qf, kf, vf, pqf, pkf):
    out, _ = _g_fwd_call(qf, kf, vf, pqf, pkf, causal, bq, bk, interpret)
    return out


def _routed_gathered_fwd(causal, bq, bk, interpret, qf, kf, vf, pqf, pkf):
    out, lse = _g_fwd_call(qf, kf, vf, pqf, pkf, causal, bq, bk, interpret)
    return out, (qf, kf, vf, pqf, pkf, out, lse)


def _routed_gathered_bwd(causal, bq, bk, interpret, res, do):
    qf, kf, vf, pqf, pkf, out, lse = res
    dq, dk, dv = _g_bwd_call(qf, kf, vf, pqf, pkf, out, lse, do, causal,
                             bq, bk, interpret)
    return dq, dk, dv, float0_like(pqf), float0_like(pkf)


_routed_gathered.defvjp(_routed_gathered_fwd, _routed_gathered_bwd)


def routed_attention_blocks(qg, kg, vg, pos_q, pos_k, causal=True,
                            valid_k=None, bq=128, bk=128,
                            interpret=None):
    """qg/kg/vg: (B,H,k,w,dh); pos_q/pos_k: (B,H,k,w) -> (B,H,k,w,dh).

    Differentiable (custom flash-style VJP). ``interpret=None`` derives
    from the platform (compiled on TPU, interpret elsewhere)."""
    B, H, kc, w, dh = qg.shape
    bq = min(bq, w)
    bk = min(bk, w)
    assert w % bq == 0 and w % bk == 0, (w, bq, bk)
    n = B * H * kc
    qf = qg.reshape(n, w, dh)
    kf = kg.reshape(n, w, dh)
    vf = vg.reshape(n, w, dh)
    pqf = pos_q.reshape(n, 1, w).astype(jnp.int32)
    pkf = pos_k.reshape(n, 1, w).astype(jnp.int32)
    if valid_k is not None:
        pkf = jnp.where(valid_k.reshape(n, 1, w), pkf, SENTINEL)
    out = _routed_gathered(bool(causal), int(bq), int(bk),
                           default_interpret(interpret), qf, kf, vf, pqf,
                           pkf)
    return out.reshape(B, H, kc, w, dh)


# ---------------------------------------------------------------------------
# Fused gather-free kernel: sequence-layout q/k/v, member rows streamed by
# per-row DMA through revolving double-buffered VMEM slots
# ---------------------------------------------------------------------------
def _dma_start_rows(src, plane, idx_ref, base, rows, dst, sem):
    """Issue one-row async copies ``src[plane, idx[base+j]] -> dst[j]``
    for j < rows, all signalling the same semaphore. Cluster membership
    has no sequence locality, so rows — not contiguous chunks — are the
    DMA unit; the cluster's SMEM index block drives the source addresses
    (the same trick the paged decode kernel uses)."""
    def body(j, _):
        row = idx_ref[0, 0, 0, base + j]
        pltpu.make_async_copy(src.at[plane, pl.ds(row, 1)],
                              dst.at[pl.ds(j, 1)], sem).start()
        return 0
    jax.lax.fori_loop(0, rows, body, 0, unroll=False)


def _dma_wait_rows(src, rows, dst, sem):
    """Wait the ``rows`` one-row copies previously started into ``dst``
    (the wait descriptor only needs the byte count, so src row 0 serves
    for every j)."""
    def body(j, _):
        pltpu.make_async_copy(src.at[0, pl.ds(0, 1)],
                              dst.at[pl.ds(j, 1)], sem).wait()
        return 0
    jax.lax.fori_loop(0, rows, body, 0, unroll=False)


def _unpack(refs, shared, n_tail):
    """Split a fused kernel's refs into (q, k, v) sources and the rest;
    shared-QK reads keys from the q plane."""
    if shared:
        q_src, v_src, *rest = refs
        k_src = q_src
    else:
        q_src, k_src, v_src, *rest = refs
    assert len(rest) == n_tail, (len(rest), n_tail)
    return q_src, k_src, v_src, rest


def _fwd_kernel(qi_ref, ki_ref, pq_ref, pk_ref, *refs, shared, causal,
                scale, bq, bk, resident):
    q_src, k_src, v_src, rest = _unpack(refs, shared, 11)
    (o_ref, lse_ref, qt_ref, kt_ref, vt_ref, m_ref, l_ref, acc_ref,
     q_sem, k_sem, v_sem) = rest
    plane = 0 if resident else pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    def start_kv(t, slot):
        _dma_start_rows(k_src, plane, ki_ref, t * bk, bk, kt_ref.at[slot],
                        k_sem.at[slot])
        _dma_start_rows(v_src, plane, ki_ref, t * bk, bk, vt_ref.at[slot],
                        v_sem.at[slot])

    @pl.when(ik == 0)
    def _prologue():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        _dma_start_rows(q_src, plane, qi_ref, iq * bq, bq, qt_ref, q_sem)
        start_kv(0, 0)
        _dma_wait_rows(q_src, bq, qt_ref, q_sem)

    # double-buffer: tile ik+1's DMAs are in flight while tile ik computes
    @pl.when(ik + 1 < nk)
    def _prefetch():
        start_kv(ik + 1, (ik + 1) % 2)

    slot = ik % 2
    _dma_wait_rows(k_src, bk, kt_ref.at[slot], k_sem.at[slot])
    _dma_wait_rows(v_src, bk, vt_ref.at[slot], v_sem.at[slot])

    q = qt_ref[...]
    k = kt_ref[slot]
    v = vt_ref[slot]
    s = _dot(q, k, 1, 1) * scale
    keep = _keep_mask(pq_ref[0, 0, 0], pk_ref[0, 0, 0], causal)
    s = jnp.where(keep, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + \
        _dot(p, v, 1, 0)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_ref[...] + jnp.log(l)


def _dq_kernel(qi_ref, ki_ref, pq_ref, pk_ref, *refs, shared, causal,
               scale, bq, bk, resident):
    q_src, k_src, v_src, rest = _unpack(refs, shared, 11)
    (do_ref, lse_ref, dsum_ref, dq_ref, qt_ref, kt_ref, vt_ref, dq_acc,
     q_sem, k_sem, v_sem) = rest
    plane = 0 if resident else pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    def start_kv(t, slot):
        _dma_start_rows(k_src, plane, ki_ref, t * bk, bk, kt_ref.at[slot],
                        k_sem.at[slot])
        _dma_start_rows(v_src, plane, ki_ref, t * bk, bk, vt_ref.at[slot],
                        v_sem.at[slot])

    @pl.when(ik == 0)
    def _prologue():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        _dma_start_rows(q_src, plane, qi_ref, iq * bq, bq, qt_ref, q_sem)
        start_kv(0, 0)
        _dma_wait_rows(q_src, bq, qt_ref, q_sem)

    @pl.when(ik + 1 < nk)
    def _prefetch():
        start_kv(ik + 1, (ik + 1) % 2)

    slot = ik % 2
    _dma_wait_rows(k_src, bk, kt_ref.at[slot], k_sem.at[slot])
    _dma_wait_rows(v_src, bk, vt_ref.at[slot], v_sem.at[slot])

    q = qt_ref[...]
    k = kt_ref[slot]
    v = vt_ref[slot]
    do = do_ref[0, 0].astype(jnp.float32)
    keep = _keep_mask(pq_ref[0, 0, 0], pk_ref[0, 0, 0], causal)
    s = _dot(q, k, 1, 1) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0, 0][:, None]), 0.0)
    dp = _dot(do, v, 1, 1)
    ds = p * (dp - dsum_ref[0, 0, 0][:, None]) * scale
    dq_acc[...] += _dot(ds, k, 1, 0)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_acc[...]


def _dkv_kernel(qi_ref, ki_ref, pq_ref, pk_ref, *refs, shared, causal,
                scale, bq, bk, resident):
    q_src, k_src, v_src, rest = _unpack(refs, shared, 13)
    (do_ref, lse_ref, dsum_ref, dk_ref, dv_ref, qt_ref, kt_ref, vt_ref,
     dk_acc, dv_acc, q_sem, k_sem, v_sem) = rest
    plane = 0 if resident else pl.program_id(0)
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    # swapped roles: the k/v tile is the single resident (it is revisited
    # by every q sweep step), the q tiles revolve through double buffers
    @pl.when(iq == 0)
    def _prologue():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        _dma_start_rows(k_src, plane, ki_ref, ik * bk, bk, kt_ref, k_sem)
        _dma_start_rows(v_src, plane, ki_ref, ik * bk, bk, vt_ref, v_sem)
        _dma_start_rows(q_src, plane, qi_ref, 0, bq, qt_ref.at[0],
                        q_sem.at[0])
        _dma_wait_rows(k_src, bk, kt_ref, k_sem)
        _dma_wait_rows(v_src, bk, vt_ref, v_sem)

    @pl.when(iq + 1 < nq)
    def _prefetch():
        _dma_start_rows(q_src, plane, qi_ref, (iq + 1) * bq, bq,
                        qt_ref.at[(iq + 1) % 2], q_sem.at[(iq + 1) % 2])

    slot = iq % 2
    _dma_wait_rows(q_src, bq, qt_ref.at[slot], q_sem.at[slot])

    q = qt_ref[slot]
    k = kt_ref[...]
    v = vt_ref[...]
    do = do_ref[0, 0].astype(jnp.float32)
    keep = _keep_mask(pq_ref[0, 0, 0], pk_ref[0, 0, 0], causal)
    s = _dot(q, k, 1, 1) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0, 0][:, None]), 0.0)
    dv_acc[...] += _dot(p, do, 0, 0)
    dp = _dot(do, v, 1, 1)
    ds = p * (dp - dsum_ref[0, 0, 0][:, None]) * scale
    dk_acc[...] += _dot(ds, q, 0, 0)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


def _fused_in_specs(N, dh, w, bq, bk, shared, resident, swapped):
    """in_specs shared by the three fused kernels: the cluster's index
    blocks (SMEM), its member positions (VMEM rows), then the q [k] v
    sources — the whole (N, dh) plane of the batch·head when resident,
    untouched HBM (ANY) when paged."""
    if swapped:                                   # grid (b, c, ik, iq)
        tq = lambda b, c, ik, iq: (b, c, 0, iq)
        tk = lambda b, c, ik, iq: (b, c, 0, ik)
    else:                                         # grid (b, c, iq, ik)
        tq = lambda b, c, iq, ik: (b, c, 0, iq)
        tk = lambda b, c, iq, ik: (b, c, 0, ik)
    idx = pl.BlockSpec((1, 1, 1, w), lambda b, c, i2, i3: (b, c, 0, 0),
                       memory_space=pltpu.SMEM)
    if resident:
        src = pl.BlockSpec((1, N, dh), lambda b, c, i2, i3: (b, 0, 0))
    else:
        src = pl.BlockSpec(memory_space=pl.ANY)
    return ([idx, idx, pl.BlockSpec((1, 1, 1, bq), tq),
             pl.BlockSpec((1, 1, 1, bk), tk)]
            + [src] * (2 if shared else 3))


def _row_blocks(bq, dh, swapped):
    """(tile, row-stat) BlockSpecs of the per-cluster q-side arrays."""
    if swapped:
        at = lambda b, c, ik, iq: (b, c, iq, 0)
        rat = lambda b, c, ik, iq: (b, c, 0, iq)
    else:
        at = lambda b, c, iq, ik: (b, c, iq, 0)
        rat = lambda b, c, iq, ik: (b, c, 0, iq)
    return (pl.BlockSpec((1, 1, bq, dh), at),
            pl.BlockSpec((1, 1, 1, bq), rat))


def _sems(q2, kv2):
    dma = pltpu.SemaphoreType.DMA
    return [dma((2,)) if q2 else dma] + [dma((2,)) if kv2 else dma] * 2


def _fused_params(N, dh, shared, resident):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=fused_vmem_limit(N, dh, 2 if shared else 3,
                                          resident))


def _fused_fwd_call(qf, kf, vf, qi, ki, pqg, pkg, shared, causal, bq, bk,
                    resident, interpret):
    BH, N, dh = qf.shape
    _, kc, _, w = qi.shape
    f32 = jnp.float32
    o_at, lse_at = _row_blocks(bq, dh, swapped=False)
    srcs = (qf,) + (() if shared else (kf,)) + (vf,)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, shared=shared, causal=causal,
                          scale=1.0 / (dh ** 0.5), bq=bq, bk=bk,
                          resident=resident),
        grid=(BH, kc, w // bq, w // bk),
        in_specs=_fused_in_specs(N, dh, w, bq, bk, shared, resident,
                                 swapped=False),
        out_specs=[o_at, lse_at],
        out_shape=[
            jax.ShapeDtypeStruct((BH, kc, w, dh), f32),
            jax.ShapeDtypeStruct((BH, kc, 1, w), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dh), f32),
            pltpu.VMEM((2, bk, dh), f32),
            pltpu.VMEM((2, bk, dh), f32),
            pltpu.VMEM((bq,), f32),
            pltpu.VMEM((bq,), f32),
            pltpu.VMEM((bq, dh), f32),
        ] + _sems(q2=False, kv2=True),
        compiler_params=_fused_params(N, dh, shared, resident),
        interpret=interpret,
    )(qi, ki, pqg, pkg, *srcs)
    return out, lse


def _fused_bwd_call(qf, kf, vf, qi, ki, pqg, pkg, out, lse, do, shared,
                    causal, bq, bk, resident, interpret):
    BH, N, dh = qf.shape
    _, kc, _, w = qi.shape
    f32 = jnp.float32
    nq, nk = w // bq, w // bk
    # the softmax backward's row sums, in XLA: the routing stage's
    # attention work, outside the kernel's span
    with span("routing/attend"):
        dsum = (do * out).sum(-1)[:, :, None, :]
    kern_kw = dict(shared=shared, causal=causal, scale=1.0 / (dh ** 0.5),
                   bq=bq, bk=bk, resident=resident)
    params = _fused_params(N, dh, shared, resident)
    srcs = (qf,) + (() if shared else (kf,)) + (vf,)
    operands = (qi, ki, pqg, pkg) + srcs + (do, lse, dsum)

    q_at, r_at = _row_blocks(bq, dh, swapped=False)
    dqg = pl.pallas_call(
        functools.partial(_dq_kernel, **kern_kw),
        grid=(BH, kc, nq, nk),
        in_specs=_fused_in_specs(N, dh, w, bq, bk, shared, resident,
                                 swapped=False) + [q_at, r_at, r_at],
        out_specs=q_at,                                   # dqg blocks
        out_shape=jax.ShapeDtypeStruct((BH, kc, w, dh), f32),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), f32),
            pltpu.VMEM((2, bk, dh), f32),
            pltpu.VMEM((2, bk, dh), f32),
            pltpu.VMEM((bq, dh), f32),
        ] + _sems(q2=False, kv2=True),
        compiler_params=params,
        interpret=interpret,
    )(*operands)

    # swapped grid: key tile parallel over (b, c, ik), query sweep inner
    q_at2, r_at2 = _row_blocks(bq, dh, swapped=True)
    k_out = pl.BlockSpec((1, 1, bk, dh), lambda b, c, ik, iq: (b, c, ik, 0))
    dkg, dvg = pl.pallas_call(
        functools.partial(_dkv_kernel, **kern_kw),
        grid=(BH, kc, nk, nq),
        in_specs=_fused_in_specs(N, dh, w, bq, bk, shared, resident,
                                 swapped=True) + [q_at2, r_at2, r_at2],
        out_specs=[k_out, k_out],
        out_shape=[
            jax.ShapeDtypeStruct((BH, kc, w, dh), f32),
            jax.ShapeDtypeStruct((BH, kc, w, dh), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bq, dh), f32),
            pltpu.VMEM((bk, dh), f32),
            pltpu.VMEM((bk, dh), f32),
            pltpu.VMEM((bk, dh), f32),
            pltpu.VMEM((bk, dh), f32),
        ] + _sems(q2=True, kv2=False),
        compiler_params=params,
        interpret=interpret,
    )(*operands)

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _routed_fused(shared, causal, bq, bk, resident, interpret, qf, kf, vf,
                  qi, ki, pqg, pkg):
    out, _ = _fused_fwd_call(qf, kf, vf, qi, ki, pqg, pkg, shared, causal,
                             bq, bk, resident, interpret)
    return out


def _routed_fused_fwd(shared, causal, bq, bk, resident, interpret, qf, kf,
                      vf, qi, ki, pqg, pkg):
    out, lse = _fused_fwd_call(qf, kf, vf, qi, ki, pqg, pkg, shared,
                               causal, bq, bk, resident, interpret)
    return out, (qf, kf, vf, qi, ki, pqg, pkg, out, lse)


def _routed_fused_bwd(shared, causal, bq, bk, resident, interpret, res, do):
    qf, kf, vf, qi, ki, pqg, pkg, out, lse = res
    dq, dk, dv = _fused_bwd_call(qf, kf, vf, qi, ki, pqg, pkg, out, lse, do,
                                 shared, causal, bq, bk, resident, interpret)
    return (dq, dk, dv, float0_like(qi), float0_like(ki),
            float0_like(pqg), float0_like(pkg))


_routed_fused.defvjp(_routed_fused_fwd, _routed_fused_bwd)


def routed_attention_fused(q, k, v, q_idx, k_idx, positions, causal=True,
                           kvalid=None, bq=128, bk=128, interpret=None,
                           paged=None):
    """Gather-free routed attention on sequence-layout tensors.

    q/v: (B,H,N,dh); k: like q, or None for shared-QK causal mode (keys
    are read from the q plane).
    q_idx/k_idx: (B,H,k,w) sorted membership indices into the sequence.
    positions: (B,N) int32 original positions (the causal mask compares
    these). kvalid: (B,N) bool, True = attendable key (padding False).
    Returns per-cluster outputs (B,H,k,w,dh); callers scatter them back.

    ``paged=None`` auto-selects the memory plan: whole-plane VMEM
    residency while the planes fit the byte budget of
    ``fused_paged_default``, per-row DMA straight from HBM beyond it (VMEM
    bounded by the tile sizes, not N). Pass True/False to force a plan.
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
    f32 = jnp.float32
    qf = q.reshape(B * H, N, dh).astype(f32)
    kf = qf if shared else k.reshape(B * H, N, dh).astype(f32)
    vf = v.reshape(B * H, N, dh).astype(f32)
    qi = q_idx.reshape(B * H, kc, 1, w).astype(jnp.int32)
    ki = k_idx.reshape(B * H, kc, 1, w).astype(jnp.int32)
    posq = positions.astype(jnp.int32)
    posk = (jnp.where(kvalid, posq, SENTINEL) if kvalid is not None
            else posq)

    def member_pos(pos, idx):
        src = jnp.broadcast_to(pos[:, None, :], (B, H, N)).reshape(B * H, N)
        return jnp.take_along_axis(src, idx.reshape(B * H, kc * w),
                                   axis=1).reshape(B * H, kc, 1, w)

    resident = not fused_paged_default(N, dh, 2 if shared else 3, paged)
    # XLA's gathers of the members' positions take the routing stage's
    # span, so the kernel's span holds the kernel's own time
    with span("routing/gather"):
        pqg, pkg = member_pos(posq, qi), member_pos(posk, ki)
    out = _routed_fused(shared, bool(causal), int(bq), int(bk), resident,
                        default_interpret(interpret), qf, kf, vf, qi, ki,
                        pqg, pkg)
    return out.reshape(B, H, kc, w, dh).astype(q.dtype)
