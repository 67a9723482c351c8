"""Pure-jnp oracles for the flash and local Pallas kernels (the ground
truth in tests; the routing kernel's is ``core.routing._block_attention``).

These are *definitions*, deliberately naive: O(n^2) materialized logits with
fp32 softmax. The framework's XLA paths (core/attention.py etc.) are
separately tested against these same semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BIG_NEG = -1e9


def flash_attention_ref(q, k, v, causal=True):
    """q: (B,H,N,dh); k,v: (B,Hkv,M,dh) -> (B,H,N,dh)."""
    B, H, N, dh = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, N, dh)
    s = jnp.einsum("bhgnd,bhmd->bhgnm", qg, k).astype(jnp.float32)
    s = s / jnp.sqrt(dh)
    if causal:
        mask = jnp.arange(N)[:, None] >= jnp.arange(M)[None, :]
        s = jnp.where(mask, s, _BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgnm,bhmd->bhgnd", p.astype(v.dtype), v)
    return o.reshape(B, H, N, dh)


def local_attention_ref(q, k, v, window, causal=True):
    """Blocked local attention: block b attends blocks {b-1, b} (causal)."""
    B, H, N, dh = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    w = min(window, N)
    assert N % w == 0, "ref requires N divisible by window"
    pos = jnp.arange(N)
    blk = pos // w
    diff = blk[:, None] - blk[None, :]
    if causal:
        keep = (diff >= 0) & (diff <= 1) & (pos[:, None] >= pos[None, :])
    else:
        keep = jnp.abs(diff) <= 1          # blocks b-1, b, b+1
    qg = q.reshape(B, Hkv, g, N, dh)
    s = jnp.einsum("bhgnd,bhmd->bhgnm", qg, k).astype(jnp.float32)
    s = jnp.where(keep, s / jnp.sqrt(dh), _BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgnm,bhmd->bhgnd", p.astype(v.dtype), v)
    return o.reshape(B, H, N, dh)

