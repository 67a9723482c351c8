"""repro.attn — the unified attention-backend API.

One entry point for every attention site in the system::

    from repro import attn
    spec = attn.spec_for_layer(cfg, "local+routing")
    out = attn.attend(spec, q, k, v, state=kmu, positions=pos,
                      pad_mask=pm)                    # train / prefill
    out = attn.attend(spec, q, k, v, state=kmu, cache=cache,
                      pos=pos)                        # decode, one token

``attend`` resolves the best registered backend for the current platform
(Pallas kernels on TPU, chunked/online-softmax references elsewhere);
``impl=`` forces a specific backend and raises a loud
``BackendResolutionError`` when its declared capabilities don't cover
the call. The registry (``repro.attn.registry``) is where new variants
and backends plug in; every registered backend must pass the parity
matrix in tests/test_attn_registry.py. See DESIGN.md §8.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax

from repro.attn import backends as _backends           # noqa: F401 (registers)
from repro.attn import registry
from repro.attn.registry import (Backend, BackendResolutionError,  # noqa
                                 CacheLayout, Capabilities, backends_for,
                                 cache_head_axes, cache_reset_values,
                                 get, pageable_cache_leaves, registered,
                                 resolve, unregister)
from repro.attn.spec import (AttentionSpec, head_split,  # noqa: F401
                             resolve_chunk, seq_shardable, spec_for_layer,
                             specs_for_model, variant_for_layer)
from repro.kernels.common import default_interpret as _default_interpret


class AttnOutput(NamedTuple):
    out: jax.Array                  # (B, H, N, dh)
    state: Optional[jax.Array]      # updated centroids (routing variants)
    cache: Optional[dict] = None    # updated decode cache (decode calls)
    stats: Optional[object] = None  # obs.RoutingStats (routing variants
    #                                 with RoutingConfig.stats=True)


def _platform(platform: Optional[str]) -> str:
    """Resolution platform: the explicit arg, else the backend the
    program runs on. Kernels still compile only where the program really
    runs on a TPU (``interpret`` derives from the real backend), so a
    caller that resolves for "tpu" on a CPU host runs the TPU backends in
    interpret mode."""
    return platform or jax.default_backend()


def _grad_guard(out, name):
    """Identity in the forward; the backward raises the registry error.

    jax.grad can reach an attend call that never announced needs_grad
    (eval code reused inside a loss, a forced impl on the train path).
    Without this, differentiating a non-VJP Pallas backend dies deep in
    tracing with an opaque missing-transpose error; with it, the failure
    is a BackendResolutionError naming the backend and the fix.
    """
    @jax.custom_vjp
    def guard(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        raise BackendResolutionError(
            f"backend {name} is not differentiable (supports_grad=False);"
            f" jax.grad through attn.attend needs a supports_grad backend"
            f" — use impl='xla', a kernel with a custom VJP, or pass"
            f" needs_grad=True to resolve one automatically")

    guard.defvjp(fwd, bwd)
    return guard(out)


def attend(spec: AttentionSpec, q, k, v, *, state=None, positions=None,
           pad_mask=None, update_state: bool = True, cache=None, pos=None,
           mesh=None, impl: Optional[str] = None,
           needs_grad: bool = False,
           platform: Optional[str] = None) -> AttnOutput:
    """Run the attention ``spec`` describes on q/k/v (un-roped, GQA head
    counts), through the best registered backend.

    Train/prefill mode (``cache=None``): returns (out, new_state).
    Decode mode (``cache`` given): q/k/v are one token (N=1), ``pos``
    (B,) is its position; returns the updated cache. ``state`` carries
    the layer's k-means centroids for routing variants in both modes.
    ``needs_grad``: the caller will differentiate through ``out`` (train
    paths announce this); resolution then excludes — or, forced, loudly
    refuses — backends without a VJP. Even without the announcement, a
    non-differentiable backend's output is guarded so jax.grad raises a
    clear BackendResolutionError instead of an opaque tracing failure.
    """
    plat = _platform(platform)
    interpret = _default_interpret(None)
    if cache is not None:
        if pad_mask is not None:
            # decode validity lives in the cache (ring positions, page
            # lengths); accepting a pad_mask here and ignoring it would be
            # exactly the silent-wrong-math failure the registry exists
            # to kill
            raise ValueError("attend(cache=...) is single-token decode; "
                             "pad_mask is not meaningful there (validity "
                             "is tracked inside the cache)")
        backend = resolve(spec, decode=True, mesh=mesh, impl=impl,
                          platform=plat)
        out, new_cache = backend.decode(spec, q, k, v, cache=cache, pos=pos,
                                        state=state, interpret=interpret)
        return AttnOutput(out=out, state=state, cache=new_cache)
    backend = resolve(spec, padded=pad_mask is not None,
                      positioned=positions is not None,
                      needs_grad=needs_grad, mesh=mesh, impl=impl,
                      platform=plat)
    res = backend.apply(spec, q, k, v, state=state,
                        positions=positions, pad_mask=pad_mask,
                        update_state=update_state,
                        interpret=interpret)
    # 2-tuple (out, new_state) or 3-tuple (out, new_state, stats):
    # routing backends surface the RoutingStats aux; everyone else
    # (including externally registered backends) stays on the 2-tuple
    out, new_state = res[0], res[1]
    stats = res[2] if len(res) > 2 else None
    if not backend.caps.supports_grad:
        out = _grad_guard(out, backend.name)
    return AttnOutput(out=out, state=new_state, stats=stats)


def decode_backend(spec: AttentionSpec, *, mesh=None,
                   impl: Optional[str] = None,
                   platform: Optional[str] = None) -> Backend:
    """The backend decode calls for ``spec`` will resolve to (the serve
    engine uses this to build cache layouts and for observability)."""
    return resolve(spec, decode=True, mesh=mesh, impl=impl,
                   platform=_platform(platform))


def init_decode_cache(spec: AttentionSpec, B: int, max_len: int, dtype, *,
                      mesh=None, impl: Optional[str] = None):
    """The cache-leaf dict declared by the resolved decode backend."""
    return decode_backend(spec, mesh=mesh, impl=impl).layout.init(
        spec, B, max_len, dtype)


def prefill_cache(spec: AttentionSpec, cache, q, k, v, *, positions,
                  state=None, mesh=None, impl: Optional[str] = None):
    """Fill the decode cache from prefix q/k/v, per the resolved decode
    backend's layout."""
    return decode_backend(spec, mesh=mesh, impl=impl).layout.fill(
        spec, cache, q, k, v, positions=positions, state=state)
