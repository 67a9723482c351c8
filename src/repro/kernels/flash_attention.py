"""Causal flash attention — Pallas TPU kernel, differentiable.

Canonical TPU shape: grid (B*H, Nq/bq, Mk/bk) with the KV dimension as the
*sequential* (arbitrary) axis; running-softmax statistics (m, l) and the
output accumulator live in VMEM scratch across the KV sweep, so no (N x M)
score matrix ever exists in HBM. Causal blocks strictly above the diagonal
are skipped with pl.when (on hardware Mosaic elides them; the roofline model
counts 2x fewer FLOPs than dense attention accordingly).

GQA: the KV BlockSpec index-maps query-head bh -> kv head (bh % H) // g, so
no repeated KV is materialized.

Backward (``jax.custom_vjp``, flash style): the forward additionally emits
per-row log-sum-exp stats (lse = m + log l, one fp32 per query row); the
backward *recomputes* each probability tile as exp(s - lse) instead of
storing any (N x M) matrix, then runs two kernels over the same block
structure: a dq kernel (grid (B*H, Nq/bq, Mk/bk), KV sequential, dq tile
accumulated in VMEM) and a dk/dv kernel (grid (B*H, Mk/bk, Nq/bq), Q
sequential). dk/dv are produced per *query* head and group-summed to the
GQA kv heads in XLA (one cheap reshape-sum, no kernel-side cross-head
accumulation).

VMEM budget per grid point (bq = bk = 128, dh <= 256, fp32 accumulators):
q/k/v tiles 3*128*256*4B = 384 KiB + acc 128*256*4B = 128 KiB + stats — well
under the ~16 MiB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG as _NEG
from repro.kernels.common import default_interpret


def _causal_iota(bq, bk, iq, ik):
    pos_q = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    pos_k = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return pos_q >= pos_k


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            bq, bk, causal, scale):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip KV blocks strictly in the future of the whole Q block
    run = (ik * bk <= iq * bq + (bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # (bq, dh)
        k = k_ref[0].astype(jnp.float32)             # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = s * scale                                # (bq, bk)
        if causal:
            keep = _causal_iota(bq, bk, iq, ik)
            s = jnp.where(keep, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
                   dq_acc, *, bq, bk, causal, scale):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (ik * bk <= iq * bq + (bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dsum = dsum_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            p = jnp.where(_causal_iota(bq, bk, iq, ik), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - dsum[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = dq_acc[...]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, bq, bk, causal,
                    scale):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (iq * bq + (bq - 1) >= ik * bk) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dsum = dsum_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            p = jnp.where(_causal_iota(bq, bk, iq, ik), p, 0.0)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - dsum[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def _flatten(q, k, v):
    B, H, N, dh = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    return (q.reshape(B * H, N, dh), k.reshape(B * Hkv, M, dh),
            v.reshape(B * Hkv, M, dh))


def _kv_index(H, Hkv):
    g = H // Hkv

    def index(bh, iq, ik):
        return ((bh // H) * Hkv + (bh % H) // g, ik, 0)
    return index


def _fwd_call(q, k, v, causal, bq, bk, interpret):
    B, H, N, dh = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    qf, kf, vf = _flatten(q, k, v)
    kv_index = _kv_index(H, Hkv)
    grid = (B * H, N // bq, M // bk)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                          scale=1.0 / (dh ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, N, dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, N), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, N, dh), lse


def _bwd_call(q, k, v, out, lse, do, causal, bq, bk, interpret):
    B, H, N, dh = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    g = H // Hkv
    qf, kf, vf = _flatten(q, k, v)
    dof = do.reshape(B * H, N, dh)
    dsum = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dsum = dsum.reshape(B * H, 1, N)
    kv_index = _kv_index(H, Hkv)
    scale = 1.0 / (dh ** 0.5)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    q_at = lambda bh, iq, ik: (bh, iq, 0)
    r_at = lambda bh, iq, ik: (bh, 0, iq)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=(B * H, N // bq, M // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_at),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bq, dh), q_at),
            pl.BlockSpec((1, 1, bq), r_at),
            pl.BlockSpec((1, 1, bq), r_at),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), q_at),
        out_shape=jax.ShapeDtypeStruct((B * H, N, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dsum)

    # dk/dv per *query* head; the kv-head group sum happens below in XLA
    q_at2 = lambda bh, ik, iq: (bh, iq, 0)
    r_at2 = lambda bh, ik, iq: (bh, 0, iq)
    kv_at2 = lambda bh, ik, iq: kv_index(bh, 0, ik)
    k_out = lambda bh, ik, iq: (bh, ik, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=(B * H, M // bk, N // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_at2),
            pl.BlockSpec((1, bk, dh), kv_at2),
            pl.BlockSpec((1, bk, dh), kv_at2),
            pl.BlockSpec((1, bq, dh), q_at2),
            pl.BlockSpec((1, 1, bq), r_at2),
            pl.BlockSpec((1, 1, bq), r_at2),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dh), k_out),
            pl.BlockSpec((1, bk, dh), k_out),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, M, dh), jnp.float32),
            jax.ShapeDtypeStruct((B * H, M, dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dh), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dsum)

    dq = dq.reshape(B, H, N, dh).astype(q.dtype)
    dk = dk.reshape(B, Hkv, g, M, dh).sum(2).astype(k.dtype)
    dv = dv.reshape(B, Hkv, g, M, dh).sum(2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(causal, bq, bk, interpret, q, k, v):
    out, _ = _fwd_call(q, k, v, causal, bq, bk, interpret)
    return out


def _flash_fwd(causal, bq, bk, interpret, q, k, v):
    out, lse = _fwd_call(q, k, v, causal, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, bq, bk, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd_call(q, k, v, out, lse, do, causal, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret=None) -> jax.Array:
    """q: (B,H,N,dh); k,v: (B,Hkv,M,dh) -> (B,H,N,dh). Differentiable."""
    N, M = q.shape[2], k.shape[2]
    bq = min(bq, N)
    bk = min(bk, M)
    assert N % bq == 0 and M % bk == 0, (N, bq, M, bk)
    return _flash(bool(causal), int(bq), int(bk),
                  default_interpret(interpret), q, k, v)
