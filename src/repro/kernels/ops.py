"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) derives from the platform: compiled
Mosaic on TPU, interpret mode everywhere else (kernels/common.py
``default_interpret``). A caller that forgets ``interpret=False`` on TPU
therefore cannot silently benchmark interpret mode, and a CPU caller
cannot crash into the Mosaic compiler. Explicit True/False still wins.

All wrappers are differentiable: the kernels carry flash-style
``jax.custom_vjp`` backwards (recompute-from-lse), so ``jax.grad``
through any of them runs Pallas end-to-end instead of falling back to
the XLA reference.

Every wrapper traces under an obs span ("kernels/<name>") so profiler
captures and HLO dumps attribute kernel time to the op, not to an
anonymous pallas_call.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _flash
from repro.kernels import local_attention as _local
from repro.kernels import routing_attention as _routing
from repro.kernels import routing_decode as _decode
from repro.obs.trace import span


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, causal=True, bq=128, bk=128, interpret=None):
    with span("kernels/flash_attention"):
        return _flash.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "causal", "interpret"))
def local_attention(q, k, v, window, causal=True, interpret=None):
    with span("kernels/local_attention"):
        return _local.local_attention_kernel(q, k, v, window, causal=causal,
                                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret", "paged"))
def routed_attention_fused(q, k, v, q_idx, k_idx, positions, causal=True,
                           kvalid=None, bq=128, bk=128, interpret=None,
                           paged=None):
    """Gather-free fused kernel: sequence-layout q/k/v (k=None reads keys
    from the q buffer — shared-QK causal mode, where k_idx is not read) +
    (B,H,k,w) membership as per-cluster SMEM blocks. Returns per-cluster
    blocks (B,H,k,w,dh).

    ``paged=None`` auto-switches the memory plan on the VMEM residency
    budget (``FUSED_RESIDENT_BYTES``): whole-plane resident below it,
    member rows DMA'd from HBM above — no sequence-length cliff; the
    cluster size w is bounded by ``FUSED_CLUSTER_BYTES``. True/False
    force a plan (a test seam: the program passes None)."""
    with span("kernels/routed_attention_fused"):
        return _routing.routed_attention_fused(
            q, k, v, q_idx, k_idx, positions, causal=causal, kvalid=kvalid,
            bq=bq, bk=bk, interpret=interpret, paged=paged)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_routing_decode(r, v_new, rk, rv, rlen, cluster, interpret=None):
    """One decoded token of routed attention over the cluster-paged cache
    (kernels/routing_decode.py): the selected page is DMA'd by its
    scalar-prefetched cluster id, never gathered in HBM."""
    with span("kernels/paged_routing_decode"):
        return _decode.paged_routing_decode(r, v_new, rk, rv, rlen, cluster,
                                            interpret=interpret)
