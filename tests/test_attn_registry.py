"""Backend-parity matrix for the repro.attn registry.

Every registered (variant, impl) pair must match the reference (xla)
backend within tolerance on causal / GQA / padded / decode cases, and a
capability-mismatched ``impl=`` override must raise loudly. This file is
run with deselect-free collection by the CI kernel-parity step (Pallas
backends execute in interpret mode on CPU), so a new backend cannot land
unregistered or untested.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attn as A
from repro.attn.registry import Backend, Capabilities
from repro.configs.base import ModelConfig, RoutingConfig
from repro.core.kmeans import init_kmeans

from conftest import FORCED_DEVICES, run_forced_devices

KEY = jax.random.PRNGKey(42)
TOL = 2e-5

# One representative spec per variant. Shapes are chosen so every Pallas
# kernel's block constraints hold (N % 128 == 0, cluster window 128).
N, DH = 256, 32
ROUTING = RoutingConfig(num_clusters=2)


def _spec(variant, *, causal=True, gqa=False):
    H, Hkv = (4, 2) if gqa else (4, 4)
    kw = dict(num_heads=H, num_kv_heads=Hkv, head_dim=DH, causal=causal)
    if variant == "full":
        return A.AttentionSpec(variant="full", **kw)
    if variant == "local":
        return A.AttentionSpec(variant="local", window=64, **kw)
    rc = ROUTING if causal else RoutingConfig(num_clusters=2, causal=False,
                                              share_qk=False)
    if variant == "routing":
        return A.AttentionSpec(variant="routing", routing=rc, **kw)
    return A.AttentionSpec(variant="local+routing", routing=rc, window=64,
                           routing_heads=2, **kw)


def _inputs(spec, key=KEY, n=N):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (2, spec.num_heads, n, spec.head_dim))
    k = jax.random.normal(ks[1], (2, spec.num_kv_heads, n, spec.head_dim))
    v = jax.random.normal(ks[2], (2, spec.num_kv_heads, n, spec.head_dim))
    Hr = spec.routing_heads or spec.num_heads
    mu = (init_kmeans(ks[3], Hr, spec.routing.num_clusters,
                      spec.head_dim).mu if spec.routing is not None
          else None)
    return q, k, v, mu


def _maxdiff(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                 .max())


def _case_kwargs(case, n=N):
    if case == "padded":
        pm = jnp.broadcast_to(jnp.arange(n)[None, :] < n - 48, (2, n))
        return {"pad_mask": pm}
    return {}


NON_REFERENCE = [b for b in A.registered() if b.impl != "xla"]


def _matrix():
    """Every non-reference backend on its own; the routing backends (all
    of them run the fused kernel's apply) again on the kernel's paged
    memory plan, which these small shapes never reach by themselves."""
    name = lambda b: b.name.replace("/", ":")
    return ([pytest.param(b, False, id=name(b)) for b in NON_REFERENCE]
            + [pytest.param(b, True, id=name(b) + ":paged")
               for b in NON_REFERENCE
               if b.variant in ("routing", "local+routing")])


MATRIX = _matrix()


@pytest.mark.parametrize("case", ["causal", "gqa", "padded"])
@pytest.mark.parametrize("backend,paged", MATRIX)
def test_backend_matches_reference(backend, paged, case, request):
    """Matrix: every non-reference backend vs the xla reference on the
    same spec/inputs. Backends whose capabilities exclude a case must
    refuse it loudly instead of computing something else."""
    if paged:
        request.getfixturevalue("paged_plan")
    spec = _spec(backend.variant, gqa=(case == "gqa"))
    q, k, v, mu = _inputs(spec)
    kwargs = _case_kwargs(case)
    if case == "padded" and not backend.caps.supports_pad_mask:
        with pytest.raises(A.BackendResolutionError, match="pad_mask"):
            A.attend(spec, q, k, v, state=mu, impl=backend.impl, **kwargs)
        return
    ref = A.attend(spec, q, k, v, state=mu, update_state=False,
                   impl="xla", **kwargs)
    out = A.attend(spec, q, k, v, state=mu, update_state=False,
                   impl=backend.impl, **kwargs)
    assert out.out.shape == ref.out.shape
    assert _maxdiff(out.out, ref.out) < TOL


@pytest.mark.parametrize("backend,paged", MATRIX)
def test_backend_grad_matches_reference(backend, paged, request):
    """Grad leg of the matrix: every supports_grad backend's jax.grad
    must match the reference's — a kernel registered with a wrong (or
    missing) VJP cannot land. supports_grad defaults to False in the
    registry precisely so this leg is the only way to claim it."""
    if not backend.caps.supports_grad:
        pytest.skip(f"{backend.name} declares supports_grad=False")
    if paged:
        request.getfixturevalue("paged_plan")
    spec = _spec(backend.variant)
    q, k, v, mu = _inputs(spec)

    def loss(impl):
        def f(q, k, v):
            out = A.attend(spec, q, k, v, state=mu, update_state=False,
                           impl=impl, needs_grad=True).out
            return (out * out).sum() / 2
        return f

    g = jax.grad(loss(backend.impl), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert float(jnp.abs(a - b).max()) < 1e-4, backend.name


@pytest.mark.parametrize("variant", ["full", "local"])
def test_decode_matches_apply(variant):
    """Decode case of the matrix: for every registered decode-capable
    backend of exact-decode variants, sequential N=1 decode through the
    declared cache layout reproduces the teacher-forced apply rows."""
    spec = _spec(variant, gqa=True)
    q, k, v, _ = _inputs(spec, n=96)
    ref = A.attend(spec, q, k, v).out
    for b in A.backends_for(variant):
        if not b.caps.supports_decode:
            continue
        cache = A.init_decode_cache(spec, 2, 96, jnp.float32,
                                    impl=b.impl)
        for t in range(96):
            pos = jnp.full((2,), t, jnp.int32)
            out = A.attend(spec, q[:, :, t:t + 1], k[:, :, t:t + 1],
                           v[:, :, t:t + 1], cache=cache, pos=pos,
                           impl=b.impl)
            cache = out.cache
            assert _maxdiff(out.out[:, :, 0], ref[:, :, t]) < 1e-4, \
                (b.name, t)


@pytest.mark.parametrize("variant", ["routing", "local+routing"])
def test_decode_cache_coherent(variant):
    """Decode case for routing variants (argmax-paged decode is the
    designed serving adaptation, not bit-equal to balanced top-k): for
    EVERY registered decode-capable backend — xla and pallas_paged ride
    the same deselect-free loop — every decoded token lands in exactly
    one page and outputs stay finite."""
    spec = _spec(variant)
    q, k, v, mu = _inputs(spec, n=32)
    ran = []
    for b in A.backends_for(variant):
        if not b.caps.supports_decode:
            continue
        ran.append(b.impl)
        assert b.layout.name in ("pages", "ring+pages")
        cache = A.init_decode_cache(spec, 2, 32, jnp.float32, impl=b.impl)
        for t in range(32):
            pos = jnp.full((2,), t, jnp.int32)
            out = A.attend(spec, q[:, :, t:t + 1], k[:, :, t:t + 1],
                           v[:, :, t:t + 1], cache=cache, pos=pos,
                           state=mu, impl=b.impl)
            cache = out.cache
            assert bool(jnp.isfinite(out.out).all()), b.name
        assert bool((cache["rlen"].sum(-1) == 32).all()), b.name
    assert "xla" in ran and "pallas_paged" in ran


# ---------------------------------------------------------------------------
# Capability enforcement
# ---------------------------------------------------------------------------
def test_forced_decode_on_apply_only_backend_raises():
    spec = _spec("full")
    q, k, v, _ = _inputs(spec)
    cache = A.init_decode_cache(spec, 2, N, jnp.float32)
    with pytest.raises(A.BackendResolutionError, match="decode"):
        A.attend(spec, q[:, :, :1], k[:, :, :1], v[:, :, :1], cache=cache,
                 pos=jnp.zeros((2,), jnp.int32), impl="pallas")


def test_explicit_positions_excluded_from_index_masking_kernels():
    """The flash kernel masks by row index; calls with caller-supplied
    positions must fall back to the positions-aware reference (auto) or
    refuse loudly (forced) — never silently mask the wrong boundary."""
    spec = _spec("full")
    q, k, v, _ = _inputs(spec)
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (2, N))
    assert A.resolve(spec, platform="tpu", positioned=True).impl == "xla"
    with pytest.raises(A.BackendResolutionError, match="positions"):
        A.attend(spec, q, k, v, positions=pos, impl="pallas")
    # positions-aware backends still take them (routing gathers pos_q/k)
    r = _spec("routing")
    q2, k2, v2, mu = _inputs(r)
    A.attend(r, q2, k2, v2, state=mu, positions=pos, impl="pallas_fused")


def test_logit_scale_excluded_from_baked_scale_backends():
    spec = A.AttentionSpec(variant="full", num_heads=4, num_kv_heads=4,
                           head_dim=DH, logit_scale=0.5)
    q, k, v, _ = _inputs(spec)
    assert A.resolve(spec, platform="tpu").impl == "xla"
    with pytest.raises(A.BackendResolutionError, match="logit_scale"):
        A.attend(spec, q, k, v, impl="pallas")
    lspec = A.AttentionSpec(variant="local", num_heads=4, num_kv_heads=4,
                            head_dim=DH, window=64, logit_scale=0.5)
    with pytest.raises(A.BackendResolutionError, match="logit_scale"):
        A.attend(lspec, q, k, v)          # no reference honors it either


def test_decode_rejects_pad_mask():
    """Decode validity lives in the cache; a pad_mask on the decode path
    would be silently ignored, so attend refuses it."""
    spec = _spec("full")
    q, k, v, _ = _inputs(spec)
    cache = A.init_decode_cache(spec, 2, N, jnp.float32)
    with pytest.raises(ValueError, match="pad_mask"):
        A.attend(spec, q[:, :, :1], k[:, :, :1], v[:, :, :1], cache=cache,
                 pos=jnp.zeros((2,), jnp.int32),
                 pad_mask=jnp.ones((2, N), bool))


def test_spec_routing_heads_field_is_authoritative():
    """AttentionSpec.routing_heads must drive the head split even when it
    disagrees with the RoutingConfig's own routing_heads knob (the spec
    is the single source of truth once built)."""
    from repro.attn.spec import head_split
    spec = A.AttentionSpec(variant="local+routing", num_heads=8,
                           num_kv_heads=8, head_dim=16, window=32,
                           routing=RoutingConfig(routing_heads=2),
                           routing_heads=6)
    assert head_split(spec) == (2, 6, 2, 6)


def test_unknown_impl_lists_registered():
    spec = _spec("full")
    q, k, v, _ = _inputs(spec)
    with pytest.raises(A.BackendResolutionError, match="pallas"):
        A.attend(spec, q, k, v, impl="cuda")
    # the routing variants register one kernel impl: "pallas" is unknown
    r = _spec("routing")
    q, k, v, mu = _inputs(r)
    with pytest.raises(A.BackendResolutionError,
                       match=r"\['pallas_fused', 'pallas_paged', 'xla'\]"):
        A.attend(r, q, k, v, state=mu, impl="pallas")


def test_unknown_variant_rejected_at_spec():
    with pytest.raises(ValueError, match="variant"):
        A.AttentionSpec(variant="strided", num_heads=4, num_kv_heads=4,
                        head_dim=32)


def test_auto_resolution_prefers_pallas_on_tpu_only():
    spec = _spec("full")
    assert A.resolve(spec, platform="cpu").impl == "xla"
    assert A.resolve(spec, platform="tpu").impl == "pallas"
    # padded calls exclude the flash kernel even on TPU
    assert A.resolve(spec, platform="tpu", padded=True).impl == "xla"


# call kind -> (resolve kwargs, the impl auto-selection must pick)
_AUTO_CALLS = {
    "apply": (dict(platform="tpu"), "pallas_fused"),
    "padded_apply": (dict(platform="tpu", padded=True), "pallas_fused"),
    "needs_grad": (dict(platform="tpu", needs_grad=True), "pallas_fused"),
    "decode": (dict(platform="tpu", decode=True), "pallas_paged"),
    "off_tpu_apply": (dict(platform="cpu"), "xla"),
    "mesh_apply": (dict(platform="tpu",
                        mesh=jax.sharding.AbstractMesh((4,), ("data",))),
                   "xla"),
}


@pytest.mark.parametrize("call", sorted(_AUTO_CALLS))
@pytest.mark.parametrize("variant", ["routing", "local+routing"])
def test_auto_resolution_table(variant, call):
    """The registry is the one place that chooses a routing kernel; this
    table pins every row of that choice. On TPU, apply, padded and
    differentiated calls take the fused kernel (priority 20, registered
    before pallas_paged, which wins the tie for decode only, where fused
    declares no decode path); off TPU and on a GSPMD mesh (every Pallas
    backend declares supports_mesh=False) the reference serves."""
    kwargs, want = _AUTO_CALLS[call]
    assert A.resolve(_spec(variant), **kwargs).impl == want


def test_mixed_local_half_uses_window_kernel(monkeypatch):
    """local+routing Pallas-family backends run the local half on the
    Pallas window kernel (which carries its own VJP — the composite
    gradient is kernel-backed end to end, covered by the matrix grad
    leg) when the case is expressible, and fall back to the XLA local
    reference when it is not (pad_mask)."""
    import repro.kernels.ops as kops
    calls = []
    orig = kops.local_attention

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(kops, "local_attention", spy)
    spec = _spec("local+routing")
    q, k, v, mu = _inputs(spec)
    A.attend(spec, q, k, v, state=mu, update_state=False,
             impl="pallas_fused")
    assert calls, "local half did not reach the Pallas window kernel"
    calls.clear()
    A.attend(spec, q, k, v, state=mu, update_state=False,
             impl="pallas_fused", **_case_kwargs("padded"))
    assert not calls, "pad_mask case must use the XLA local reference"


def test_supports_grad_capability_enforced():
    """A forced non-differentiable backend refuses needs_grad calls at
    resolution, and jax.grad through its output raises the registry
    error instead of an opaque tracing failure (the guard)."""
    spec = _spec("full")
    q, k, v, _ = _inputs(spec)
    A.registry.register(Backend(
        variant="full", impl="_test_nograd",
        apply=lambda spec, q, k, v, **kw: (q, None),
        caps=Capabilities(supports_grad=False)))
    try:
        with pytest.raises(A.BackendResolutionError, match="supports_grad"):
            A.attend(spec, q, k, v, impl="_test_nograd", needs_grad=True)
        # un-announced grad: the guard fires during backward tracing
        def loss(q):
            return A.attend(spec, q, k, v, impl="_test_nograd").out.sum()
        with pytest.raises(A.BackendResolutionError, match="supports_grad"):
            jax.grad(loss)(q)
    finally:
        A.unregister("full", "_test_nograd")


def test_builtin_pallas_backends_are_differentiable():
    """Every built-in Pallas backend carries a custom VJP now — the train
    path never silently needs the XLA reference again."""
    for b in A.registered():
        assert b.caps.supports_grad, b.name


def test_every_backend_declares_consistent_hints():
    hints = A.cache_head_axes()
    for b in A.registered():
        if b.caps.supports_decode:
            cache = b.layout.init(_spec(b.variant), 1, 32, jnp.float32)
            for leaf, arr in cache.items():
                ax = hints.get(leaf)
                assert ax is None or arr.ndim >= ax, (b.name, leaf)


def test_cache_layout_protocol():
    """The typed CacheLayout answers every layout question in one object:
    init/fill callables, reset values, head axes, pageable structure, and
    allocation-free lane-byte accounting."""
    for b in A.registered():
        if not b.caps.supports_decode:
            continue
        lo = b.layout
        spec = _spec(b.variant)
        cache = lo.init(spec, 1, 32, jnp.float32)
        nbytes = lo.lane_bytes(spec, 32, jnp.float32)
        assert nbytes == sum(np.prod(a.shape) * a.dtype.itemsize
                             for a in cache.values()), b.name
        for leaf in lo.pageable_leaves:         # pages are (…, kc, cap, dh)
            assert cache[leaf].ndim >= 4, (b.name, leaf)
            assert lo.page_len_leaf in cache, b.name
        for leaf, val in lo.reset_values.items():
            assert bool((cache[leaf] == val).all()), (b.name, leaf)
        # deprecated Backend accessors still delegate to the layout
        assert b.init_cache is lo.init and b.prefill_fill is lo.fill
        assert b.cache_head_axes == lo.head_axes


# ---------------------------------------------------------------------------
# Spec resolution (the attn_chunk satellite + degenerate splits)
# ---------------------------------------------------------------------------
def test_chunk_resolution_explicit_zero_wins_for_long_seq():
    base = dict(num_heads=4, num_kv_heads=4, head_dim=32)
    auto = A.AttentionSpec(variant="full", chunk=None, **base)
    one_shot = A.AttentionSpec(variant="full", chunk=0, **base)
    forced = A.AttentionSpec(variant="full", chunk=256, **base)
    assert A.resolve_chunk(auto, 8192) == 1024      # auto kicks in
    assert A.resolve_chunk(auto, 512) == 0
    assert A.resolve_chunk(one_shot, 8192) == 0     # 0 is now settable
    assert A.resolve_chunk(forced, 512) == 256


def test_config_chunk_flows_into_spec():
    cfg = ModelConfig(attention="full", attn_chunk=0)
    assert A.spec_for_layer(cfg, "full").chunk == 0
    cfg2 = ModelConfig(attention="full")
    assert A.spec_for_layer(cfg2, "full").chunk is None


def test_degenerate_local_routing_collapses():
    cfg = ModelConfig(num_heads=2, num_kv_heads=1,
                      attention="local+routing",
                      routing=RoutingConfig(routing_heads=2))
    assert A.spec_for_layer(cfg, "local+routing").variant == "routing"
    cfg2 = ModelConfig(num_heads=1, num_kv_heads=1,
                       attention="local+routing",
                       routing=RoutingConfig())   # H//2 == 0 -> no routing
    s2 = A.spec_for_layer(cfg2, "local+routing")
    assert s2.variant == "local"
    assert s2.window == cfg2.routing.local_window


# ---------------------------------------------------------------------------
# Mesh case of the matrix (multi-device CI lane; subprocess keeps the
# main pytest process single-device, see conftest)
# ---------------------------------------------------------------------------
def test_registry_matrix_on_mesh():
    run_forced_devices(f"""
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import attn as A
from repro.configs.base import RoutingConfig
from repro.core.kmeans import init_kmeans

D = {FORCED_DEVICES}
mesh = Mesh(jax.devices(), ("data",))
rc = RoutingConfig(num_clusters=2)
for variant in ("full", "local", "routing", "local+routing"):
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=32)
    spec = dict(
        full=A.AttentionSpec(variant="full", **kw),
        local=A.AttentionSpec(variant="local", window=64, **kw),
        routing=A.AttentionSpec(variant="routing", routing=rc, **kw),
    ).get(variant) or A.AttentionSpec(variant="local+routing", routing=rc,
                                      window=64, routing_heads=2, **kw)
    # a mesh call must resolve to a mesh-capable backend
    assert A.resolve(spec, mesh=mesh, platform="tpu").caps.supports_mesh
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (D, 4, 128, 32))
    k = jax.random.normal(ks[1], (D, 2, 128, 32))
    v = jax.random.normal(ks[2], (D, 2, 128, 32))
    mu = init_kmeans(ks[3], spec.routing_heads or 4, 2, 32).mu
    ref = A.attend(spec, q, k, v, state=mu, update_state=False).out

    sh = NamedSharding(mesh, P("data"))
    qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))
    fn = jax.jit(lambda q, k, v, mu: A.attend(
        spec, q, k, v, state=mu, update_state=False, mesh=mesh).out)
    out = fn(qs, ks_, vs, mu)
    err = float(jnp.abs(out - ref).max())
    assert err < 2e-5, (variant, err)
print("MESH-MATRIX-OK")
""")
