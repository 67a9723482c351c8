"""Attention backend registry: (variant, impl) -> Backend + capabilities.

A ``Backend`` bundles everything one implementation of one variant can
do: the train/prefill ``apply`` math, optionally a single-token
``decode`` against the typed ``CacheLayout`` it declares (cache init,
prefill fill, reset fill values, head-axis sharding hints, pageable
page structure), and a ``Capabilities`` record the resolver filters on.

Resolution order (``resolve``): among the backends registered for the
spec's variant, drop those whose capabilities don't cover the call
(decode needed, pad_mask present, mesh > 1 device, no VJP for a
differentiated call, TPU-only backend off-TPU), then take the highest
``priority`` (the first registered on a tie). This is the one place the
program chooses a kernel; the fused routing kernel then chooses its own
memory plan from N·dh (kernels/common.py). Pallas kernels register with
``needs_tpu=True`` and a priority above the references': auto-selection
prefers them on TPU and never picks them elsewhere, while an explicit
``impl=`` still runs one anywhere via interpret mode (that is what the
CPU kernel-parity CI lane exercises). Every *other* capability mismatch
on an explicit ``impl=`` override is a loud ``BackendResolutionError`` —
a forced backend silently computing the wrong thing (ignoring padding,
lacking a decode path) is the failure mode this registry exists to
kill; the error also names the backend auto-selection would have used,
so the caller knows the escape hatch.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from repro.attn.spec import AttentionSpec


class BackendResolutionError(ValueError):
    """No registered backend satisfies the call (or a forced one can't)."""


@dataclass(frozen=True)
class CacheLayout:
    """Typed decode-cache layout owned by a backend — the one object that
    answers every layout question the serving stack used to scatter
    across free functions (``serving.cache_reset_value``,
    ``registry.cache_fill_values``, ``cache_head_axes``).

    ``init(spec, B, max_len, dtype)``   build the cache-leaf dict
    ``fill(spec, cache, q, k, v, *, positions, state)``
                                        fill it from prefix q/k/v
    ``reset_values``   leaf name -> init/reset fill value (default 0);
                       ``fill_values`` is a compat alias
    ``head_axes``      leaf name -> axis carrying the head dim in POOL
                       coords (leaves are (G, B, head, ...) once stacked
                       over scan groups) — dist.sharding.cache_sharding
                       consumes the merged map
    ``pageable_leaves`` leaf names laid out as cluster pages
                       (B, H, kc, cap, ...) whose occupied prefix per
                       page is ``min(page_len_leaf, cap)`` — the tiered
                       KV store transfers/evicts these at per-page
                       granularity instead of whole-lane blobs
    ``page_len_leaf``  the (B, H, kc) int leaf counting writes per page
    ``lane_bytes``     bytes of one B=1 lane at (spec, max_len, dtype) —
                       abstract-eval'd, nothing is allocated
    """

    name: str
    init: Optional[Callable] = None
    fill: Optional[Callable] = None
    reset_values: Mapping[str, int] = field(default_factory=dict)
    head_axes: Mapping[str, int] = field(default_factory=dict)
    pageable_leaves: Tuple[str, ...] = ()
    page_len_leaf: str = ""

    @property
    def fill_values(self) -> Mapping[str, int]:
        return self.reset_values

    def reset_value(self, leaf_name: str) -> int:
        return self.reset_values.get(leaf_name, 0)

    def lane_bytes(self, spec: AttentionSpec, max_len: int, dtype) -> int:
        import jax
        import jax.numpy as jnp
        import numpy as np
        dt = jnp.dtype(dtype)
        shapes = jax.eval_shape(lambda: self.init(spec, 1, max_len, dt))
        return int(sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(shapes)))


@dataclass(frozen=True)
class Capabilities:
    """What a backend can serve. ``needs_tpu`` gates auto-selection only;
    every other flag is enforced for forced ``impl=`` overrides too.

    ``supports_positions``: the causal mask honors caller-supplied
    (non-arange) positions — kernels that mask by row/block index must
    declare False so packed-sequence calls fall back to (or loudly
    refuse into) the positions-aware reference instead of silently
    attending across the wrong boundary.
    ``supports_logit_scale``: the backend honors
    ``AttentionSpec.logit_scale``; backends with a baked 1/sqrt(dh)
    scale declare False and are excluded for specs that override it.
    ``supports_grad``: the apply path is differentiable (XLA math, or a
    kernel with a custom VJP). Deliberately defaults to False — a new
    kernel backend must *claim* differentiability (and then pass the
    grad leg of the registry parity matrix), it cannot inherit it.
    Backends at False are excluded from calls that announce
    ``needs_grad`` and — because jax.grad can reach a call that didn't
    announce it — their outputs are wrapped in a guard whose backward
    raises this registry's error instead of an opaque Pallas trace
    failure (see ``attn.attend``).
    """

    supports_decode: bool = False
    supports_mesh: bool = True
    supports_pad_mask: bool = True
    supports_positions: bool = True
    supports_logit_scale: bool = False
    supports_grad: bool = False
    needs_tpu: bool = False


@dataclass(frozen=True)
class Backend:
    """One (variant, impl) implementation.

    apply(spec, q, k, v, *, state, positions, pad_mask, update_state,
          interpret) -> (out, new_state)
          or (out, new_state, stats): routing backends return a third
          element — the obs.RoutingStats aux pytree (None unless
          RoutingConfig.stats) — and attend() accepts either arity, so
          existing 2-tuple backends keep working unchanged
    decode(spec, q, k, v, *, cache, pos, state, interpret)
          -> (out, new_cache)                      [supports_decode only]
    layout: the backend's typed CacheLayout — cache init, prefill fill,
          reset fill values, head-axis sharding hints, pageable-page
          structure, and lane-byte accounting, all in one object
          (decode-capable backends must declare one).
    """

    variant: str
    impl: str
    apply: Callable
    caps: Capabilities
    decode: Optional[Callable] = None
    layout: Optional[CacheLayout] = None
    priority: int = 0

    @property
    def key(self) -> Tuple[str, str]:
        return (self.variant, self.impl)

    @property
    def name(self) -> str:
        return f"{self.variant}/{self.impl}"

    # -- deprecated accessors (pre-CacheLayout spelling) -------------------
    @property
    def init_cache(self) -> Optional[Callable]:
        """DEPRECATED: use ``backend.layout.init``."""
        return self.layout.init if self.layout is not None else None

    @property
    def prefill_fill(self) -> Optional[Callable]:
        """DEPRECATED: use ``backend.layout.fill``."""
        return self.layout.fill if self.layout is not None else None

    @property
    def cache_head_axes(self) -> Mapping[str, int]:
        """DEPRECATED: use ``backend.layout.head_axes``."""
        return self.layout.head_axes if self.layout is not None else {}

    @property
    def cache_fill(self) -> Mapping[str, int]:
        """DEPRECATED: use ``backend.layout.reset_values``."""
        return self.layout.reset_values if self.layout is not None else {}


_REGISTRY: Dict[Tuple[str, str], Backend] = {}


def register(backend: Backend) -> Backend:
    if backend.key in _REGISTRY:
        raise ValueError(f"backend {backend.name} already registered")
    if backend.caps.supports_decode and backend.decode is None:
        raise ValueError(f"{backend.name}: supports_decode without a "
                         f"decode fn")
    if backend.caps.supports_decode and (
            backend.layout is None or backend.layout.init is None
            or backend.layout.fill is None):
        raise ValueError(f"{backend.name}: supports_decode without a "
                         f"declared CacheLayout (layout.init/layout.fill)")
    _REGISTRY[backend.key] = backend
    return backend


def unregister(variant: str, impl: str) -> None:
    """Test hook: remove a backend (e.g. a dummy registered by a test)."""
    _REGISTRY.pop((variant, impl), None)


def get(variant: str, impl: str) -> Backend:
    try:
        return _REGISTRY[(variant, impl)]
    except KeyError:
        impls = sorted(i for v, i in _REGISTRY if v == variant)
        raise BackendResolutionError(
            f"no backend registered for variant={variant!r} impl={impl!r};"
            f" registered impls for this variant: {impls or 'none'}"
        ) from None


def backends_for(variant: str) -> List[Backend]:
    return [b for b in _REGISTRY.values() if b.variant == variant]


def registered() -> List[Backend]:
    """All registered backends (benchmark sweeps, the parity matrix)."""
    return list(_REGISTRY.values())


def _layouts() -> List[CacheLayout]:
    return [b.layout for b in _REGISTRY.values() if b.layout is not None]


def cache_head_axes() -> Dict[str, int]:
    """Merged leaf-name -> head-axis map over every registered backend's
    CacheLayout (pool coords). dist.sharding consumes this instead of
    hardcoding cache leaf names."""
    hints: Dict[str, int] = {}
    for lo in _layouts():
        for leaf, axis in lo.head_axes.items():
            prev = hints.setdefault(leaf, axis)
            if prev != axis:
                raise ValueError(
                    f"conflicting head-axis hints for cache leaf "
                    f"{leaf!r}: {prev} vs {axis} (layout {lo.name!r})")
    return hints


def cache_reset_values() -> Dict[str, int]:
    """Merged leaf-name -> reset fill value over the registered layouts
    (the slot pool's reset_slot; leaves not listed reset to 0)."""
    fills: Dict[str, int] = {}
    for lo in _layouts():
        for leaf, val in lo.reset_values.items():
            prev = fills.setdefault(leaf, val)
            if prev != val:
                raise ValueError(
                    f"conflicting fill values for cache leaf {leaf!r}: "
                    f"{prev} vs {val} (layout {lo.name!r})")
    return fills


def pageable_cache_leaves() -> Dict[str, str]:
    """Merged leaf-name -> page-length-leaf map for cluster-page-
    structured cache leaves ((B, H, kc, cap, ...) with an occupied
    prefix of min(page_len, cap) per page). The tiered KV store uses
    this to park/transfer pages at per-page granularity."""
    out: Dict[str, str] = {}
    for lo in _layouts():
        for leaf in lo.pageable_leaves:
            prev = out.setdefault(leaf, lo.page_len_leaf)
            if prev != lo.page_len_leaf:
                raise ValueError(
                    f"conflicting page-length leaves for {leaf!r}: "
                    f"{prev!r} vs {lo.page_len_leaf!r} ({lo.name!r})")
    return out


def _gaps(b: Backend, *, decode: bool, padded: bool,
          positioned: bool, scaled: bool, needs_grad: bool,
          mesh_devices: int, platform: str, forced: bool) -> List[str]:
    """Capability gaps of ``b`` for this call. ``needs_tpu`` only counts
    against auto-selection (forced backends fall back to interpret)."""
    gaps = []
    if decode and not b.caps.supports_decode:
        gaps.append("call needs a decode path (cache given) but "
                    "supports_decode=False")
    if needs_grad and not b.caps.supports_grad:
        gaps.append("call is differentiated (needs_grad=True) but the "
                    "backend has no VJP (supports_grad=False)")
    if padded and not b.caps.supports_pad_mask:
        gaps.append("call has a pad_mask but supports_pad_mask=False")
    if positioned and not b.caps.supports_positions:
        gaps.append("call has explicit positions but the backend masks "
                    "by row index (supports_positions=False)")
    if scaled and not b.caps.supports_logit_scale:
        gaps.append("spec sets logit_scale but the backend's scale is "
                    "baked at 1/sqrt(head_dim) "
                    "(supports_logit_scale=False)")
    if mesh_devices > 1 and not b.caps.supports_mesh:
        gaps.append(f"call runs on a {mesh_devices}-device mesh but "
                    f"supports_mesh=False")
    if not forced and b.caps.needs_tpu and platform != "tpu":
        gaps.append(f"needs_tpu on platform {platform!r}")
    return gaps


def resolve(spec: AttentionSpec, *, decode: bool = False,
            padded: bool = False, positioned: bool = False,
            needs_grad: bool = False, seq_len: Optional[int] = None,
            mesh=None, impl: Optional[str] = None,
            platform: str = "cpu") -> Backend:
    """Pick the backend for this call, or raise loudly.

    ``impl``: explicit override — capability mismatches are errors, not
    silent fallbacks. Without it: best (highest-priority) registered
    backend whose capabilities cover the call on ``platform``.
    ``needs_grad``: the caller will differentiate through the result
    (train paths announce this) — non-differentiable backends are
    excluded / refused.
    ``seq_len`` is accepted and ignored: no backend is capped by
    sequence length (bench/tests/test_imagenet64_cell.py still passes
    it).
    """
    mesh_devices = getattr(mesh, "size", 1) if mesh is not None else 1
    gap_kw = dict(decode=decode, padded=padded, positioned=positioned,
                  needs_grad=needs_grad,
                  scaled=spec.logit_scale is not None,
                  mesh_devices=mesh_devices, platform=platform)
    if impl is not None:
        b = get(spec.variant, impl)
        gaps = _gaps(b, forced=True, **gap_kw)
        if gaps:
            msg = (f"forced backend {b.name} cannot serve this call:\n  - "
                   + "\n  - ".join(gaps))
            try:
                alt = resolve(spec, decode=decode, padded=padded,
                              positioned=positioned, needs_grad=needs_grad,
                              mesh=mesh, impl=None, platform=platform)
            except BackendResolutionError:
                alt = None
            if alt is not None:
                msg += (f"\nauto-selection (impl=None) would serve this "
                        f"call with {alt.name}")
            raise BackendResolutionError(msg)
        return b
    cands = backends_for(spec.variant)
    if not cands:
        raise BackendResolutionError(
            f"no backends registered for variant {spec.variant!r}")
    ok = [b for b in cands if not _gaps(b, forced=False, **gap_kw)]
    if not ok:
        detail = "; ".join(
            f"{b.name}: "
            f"{', '.join(_gaps(b, forced=False, **gap_kw))}"
            for b in cands)
        raise BackendResolutionError(
            f"no registered backend for variant {spec.variant!r} covers "
            f"this call ({detail})")
    return max(ok, key=lambda b: b.priority)
