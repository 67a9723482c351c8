"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True),
the custom-VJP gradient-parity suite, and the fused routing kernel against
the XLA routing reference in both memory plans. The gather-free HLO
guarantee of the fused kernel is checked on the compiled TPU program in
tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(3)
TOL = {"float32": 2e-5, "bfloat16": 3e-2}
GRAD_TOL = 1e-3


def _grad_maxdiff(g1, g2):
    return max(float(jnp.abs(a - b).max()) for a, b in zip(g1, g2))


def _mk(shape, dtype, key):
    return jax.random.normal(key, shape, dtype=jnp.dtype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,H,Hkv,N,dh,bq,bk,causal", [
    (2, 4, 2, 256, 64, 128, 128, True),
    (1, 2, 1, 128, 32, 64, 32, True),
    (2, 4, 4, 128, 128, 64, 64, False),
    (1, 8, 2, 512, 64, 128, 64, True),
])
def test_flash_attention_sweep(dtype, B, H, Hkv, N, dh, bq, bk, causal):
    ks = jax.random.split(KEY, 3)
    q = _mk((B, H, N, dh), dtype, ks[0])
    k = _mk((B, Hkv, N, dh), dtype, ks[1])
    v = _mk((B, Hkv, N, dh), dtype, ks[2])
    o = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    r = ref.flash_attention_ref(q, k, v, causal=causal)
    err = float(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32)).max())
    assert err < TOL[dtype], err


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,H,Hkv,N,dh,w,causal", [
    (2, 4, 2, 256, 64, 64, True),
    (1, 2, 1, 128, 32, 32, False),
    (2, 2, 2, 256, 128, 128, True),
    # windows past the query sub-tile (sub_tile(1024, 64) = 256 rows)
    (1, 2, 1, 2048, 64, 1024, True),
    (1, 2, 2, 2048, 64, 1024, False),
])
def test_local_attention_sweep(dtype, B, H, Hkv, N, dh, w, causal):
    ks = jax.random.split(KEY, 3)
    q = _mk((B, H, N, dh), dtype, ks[0])
    k = _mk((B, Hkv, N, dh), dtype, ks[1])
    v = _mk((B, Hkv, N, dh), dtype, ks[2])
    o = ops.local_attention(q, k, v, window=w, causal=causal)
    r = ref.local_attention_ref(q, k, v, window=w, causal=causal)
    err = float(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32)).max())
    assert err < TOL[dtype], err


def _rows(x, idx):
    """(B,H,N,d) rows at (B,H,k,w) member indices -> (B,H,k,w,d)."""
    B, H, kc, w = idx.shape
    return jnp.take_along_axis(x, idx.reshape(B, H, -1, 1),
                               axis=2).reshape(B, H, kc, w, x.shape[-1])


def _seq_at(x, idx):
    """(B,N) per-token values at (B,H,k,w) member indices."""
    B, H, kc, w = idx.shape
    src = jnp.broadcast_to(x[:, None], (B, H) + x.shape[1:])
    return jnp.take_along_axis(src, idx.reshape(B, H, -1),
                               axis=2).reshape(B, H, kc, w)


def _fused_reference(q, k, v, qi, ki, pos, causal, kvalid):
    """The XLA routing reference (core.routing._block_attention) on the
    blocks the fused kernel reads in place: k None = shared-QK keys."""
    from repro.core.routing import _block_attention
    kk, kidx = (q, qi) if k is None else (k, ki)
    vk = None if kvalid is None else _seq_at(kvalid, kidx)
    og, _ = _block_attention(_rows(q, qi), _rows(kk, kidx), _rows(v, kidx),
                             _seq_at(pos, qi), _seq_at(pos, kidx), causal,
                             vk, False)
    return og


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,H,kc,w,dh,bq,bk,causal,valid", [
    (2, 2, 4, 128, 64, 64, 64, True, False),
    (1, 2, 2, 64, 32, 32, 32, False, True),
    (1, 1, 8, 128, 128, 128, 64, True, False),
    (2, 2, 2, 64, 64, 32, 64, False, False),
])
def test_fused_sweep(dtype, B, H, kc, w, dh, bq, bk, causal, valid):
    """The fused kernel over shapes and dtypes, on sorted random members
    (repeats allowed) at random positions: causal cases share QK, the
    non-causal ones take separate keys. The reference runs in float32 on
    the same (rounded) inputs."""
    N = kc * w
    ks = jax.random.split(KEY, 7)
    q = _mk((B, H, N, dh), dtype, ks[0])
    k = None if causal else _mk((B, H, N, dh), dtype, ks[1])
    v = _mk((B, H, N, dh), dtype, ks[2])
    qi = jnp.sort(jax.random.randint(ks[3], (B, H, kc, w), 0, N), axis=-1)
    ki = None if causal else jnp.sort(
        jax.random.randint(ks[4], (B, H, kc, w), 0, N), axis=-1)
    pos = jax.random.randint(ks[5], (B, N), 0, 4096)
    kvalid = jax.random.bernoulli(ks[6], 0.85, (B, N)) if valid else None
    o = ops.routed_attention_fused(q, k, v, qi, ki, pos, causal=causal,
                                   kvalid=kvalid, bq=bq, bk=bk)
    f32 = lambda x: None if x is None else x.astype(jnp.float32)
    r = _fused_reference(f32(q), f32(k), f32(v), qi, ki, pos, causal,
                         kvalid)
    assert o.dtype == jnp.dtype(dtype)
    err = float(jnp.abs(o.astype(jnp.float32) - r).max())
    assert err < TOL[dtype], err


def test_routing_module_fused_equals_xla():
    from repro.configs.base import RoutingConfig
    from repro.core.kmeans import init_kmeans
    from repro.core.routing import routed_attention
    B, H, N, dh = 2, 4, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    v = jax.random.normal(ks[1], (B, H, N, dh))
    st = init_kmeans(ks[2], H, 4, dh)
    cfg = RoutingConfig(num_clusters=4)
    o_x = routed_attention(q, None, v, st, cfg, impl="xla").out
    o_f = routed_attention(q, None, v, st, cfg, impl="pallas_fused").out
    assert float(jnp.abs(o_x - o_f).max()) < 1e-5
    with pytest.raises(ValueError, match="impl 'pallas'"):
        routed_attention(q, None, v, st, cfg, impl="pallas")


# ---------------------------------------------------------------------------
# Gradient parity: every kernel's custom VJP vs jax.grad of the XLA math
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_parity(causal):
    B, H, Hkv, N, dh = 2, 4, 2, 256, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = jax.random.normal(ks[1], (B, Hkv, N, dh))
    v = jax.random.normal(ks[2], (B, Hkv, N, dh))
    wt = jax.random.normal(ks[3], (B, H, N, dh))
    f = lambda q, k, v: (ops.flash_attention(q, k, v, causal=causal)
                         * wt).sum()
    fr = lambda q, k, v: (ref.flash_attention_ref(q, k, v, causal=causal)
                          * wt).sum()
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    assert _grad_maxdiff(g, gr) < GRAD_TOL


@pytest.mark.parametrize("causal", [True, False])
def test_local_attention_grad_parity(causal):
    B, H, Hkv, N, dh, w = 2, 4, 2, 256, 64, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = jax.random.normal(ks[1], (B, Hkv, N, dh))
    v = jax.random.normal(ks[2], (B, Hkv, N, dh))
    wt = jax.random.normal(ks[3], (B, H, N, dh))
    f = lambda q, k, v: (ops.local_attention(q, k, v, window=w,
                                             causal=causal) * wt).sum()
    fr = lambda q, k, v: (ref.local_attention_ref(q, k, v, window=w,
                                                  causal=causal) * wt).sum()
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    assert _grad_maxdiff(g, gr) < GRAD_TOL


@pytest.mark.parametrize("H,Hkv,N,causal", [
    pytest.param(2, 1, 2048, True, id="True"),
    pytest.param(2, 1, 2048, False, id="False"),
    # three blocks: the first (clamped previous block), a middle one and
    # the last (clamped next block) each skip their own tiles
    pytest.param(4, 2, 3072, True, id="3blocks-gqa-True"),
    pytest.param(4, 2, 3072, False, id="3blocks-gqa-False"),
    pytest.param(2, 2, 3072, True, id="3blocks-True"),
])
def test_local_attention_sub_tiled_grad_parity(H, Hkv, N, causal):
    """A window larger than its query sub-tile: the forward and dq run
    four 256-row query sub-tiles of each 1024-row block against the key
    tiles the mask leaves, dk/dv four 256-row key sub-tiles against the
    query tiles that attend them."""
    from repro.kernels.local_attention import sub_tile
    B, dh, w = 1, 64, 1024
    assert sub_tile(w, dh) == 256 and sub_tile(256, 128) == 256
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = jax.random.normal(ks[1], (B, Hkv, N, dh))
    v = jax.random.normal(ks[2], (B, Hkv, N, dh))
    wt = jax.random.normal(ks[3], (B, H, N, dh))
    o = ops.local_attention(q, k, v, window=w, causal=causal)
    r = ref.local_attention_ref(q, k, v, window=w, causal=causal)
    assert float(jnp.abs(o - r).max()) < TOL["float32"]
    f = lambda q, k, v: (ops.local_attention(q, k, v, window=w,
                                             causal=causal) * wt).sum()
    fr = lambda q, k, v: (ref.local_attention_ref(q, k, v, window=w,
                                                  causal=causal) * wt).sum()
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    assert _grad_maxdiff(g, gr) < GRAD_TOL


@pytest.mark.parametrize("n,w,dh,causal,share", [
    # rt-imagenet64: 6 blocks of 16 sub-tiles of 128 rows
    (12288, 2048, 64, True, 268288 / 393216),
    # one sub-tile a block (rt-enwik8's w 256, every w <= 512): untouched
    (8192, 256, 128, True, 1.0),
    (8192, 256, 128, False, 1.0),
    (512, 512, 64, True, 1.0),
    # encoder mode skips only the two clamped edge blocks: (3·6 - 2)/(3·6)
    (12288, 2048, 64, False, 16 / 18),
    (3072, 1024, 64, False, 7 / 9),
    # one block: its own r + 1 tiles of the parent's 2·nq a sub-tile
    (1024, 1024, 64, True, 10 / 32),
])
def test_local_live_tile_share(n, w, dh, causal, share):
    """The share of the whole-window kernels' score tiles the sub-tiled
    kernels compute, counted from shapes."""
    from repro.kernels.local_attention import live_tile_share
    assert live_tile_share(n, w, dh, causal) == pytest.approx(share,
                                                              rel=1e-12)


def _routing_case(case):
    """(cfg, k_or_None, pad_mask) for a named routing parity case."""
    from repro.configs.base import RoutingConfig
    B, N = 2, 256
    pm = jnp.broadcast_to(jnp.arange(N)[None, :] < N - 37, (B, N))
    k = jax.random.normal(jax.random.PRNGKey(11), (B, 4, N, 64))
    return {
        "causal_shared": (RoutingConfig(num_clusters=4), None, None),
        "causal_shared_padded": (RoutingConfig(num_clusters=4), None, pm),
        "noncausal_separate": (RoutingConfig(num_clusters=4, causal=False,
                                             share_qk=False), k, None),
        "noncausal_padded": (RoutingConfig(num_clusters=4, causal=False,
                                           share_qk=False), k, pm),
        "causal_separate": (RoutingConfig(num_clusters=4, share_qk=False),
                            k, None),
        "segmented": (RoutingConfig(num_clusters=4, segments=2), None,
                      None),
    }[case]


@pytest.mark.parametrize("plan", ["resident", "paged"])
@pytest.mark.parametrize("case", ["causal_shared", "causal_shared_padded",
                                  "noncausal_separate", "noncausal_padded",
                                  "causal_separate", "segmented"])
def test_routing_grad_parity(plan, case, request):
    """Fused kernel outputs and VJPs, in both memory plans, vs the XLA
    reference through the full routing module, on every mask/sharing
    regime, at dh = 64 (the kernel's rows padded to 128 lanes). Causal
    separate keys read the band from other positions than the queries'."""
    from repro.core.kmeans import init_kmeans
    from repro.core.routing import routed_attention
    if plan == "paged":
        request.getfixturevalue("paged_plan")
    B, H, N, dh = 2, 4, 256, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    v = jax.random.normal(ks[1], (B, H, N, dh))
    wt = jax.random.normal(ks[3], (B, H, N, dh))
    st = init_kmeans(ks[2], H, 4, dh)
    cfg, k, pm = _routing_case(case)

    def loss(impl):
        def f(q, k, v):
            out = routed_attention(q, k, v, st, cfg, pad_mask=pm,
                                   update_state=False, impl=impl).out
            return (out * wt).sum(), out
        return f

    args = (0, 2) if k is None else (0, 1, 2)
    (_, o), g = jax.value_and_grad(loss("pallas_fused"), argnums=args,
                                   has_aux=True)(q, k, v)
    (_, o_r), gr = jax.value_and_grad(loss("xla"), argnums=args,
                                      has_aux=True)(q, k, v)
    assert float(jnp.abs(o - o_r).max()) < TOL["float32"]
    assert _grad_maxdiff(g, gr) < GRAD_TOL


# ---------------------------------------------------------------------------
# Fused kernel: forward parity with the XLA reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32)])
@pytest.mark.parametrize("shared,causal,valid", [
    (False, True, False), (False, False, True),
    (True, True, False), (True, True, True),
])
def test_fused_forward_matches_reference(shared, causal, valid, bq, bk):
    """Forward parity with the XLA routing reference at several (bq, bk):
    the fused kernel fetches each cluster's rows once and runs online
    softmax over its sub-tiles. Shared-QK mode takes the one index array
    (k_idx None)."""
    B, H, N, dh, kc, w = 2, 2, 256, 64, 4, 64
    ks = jax.random.split(KEY, 6)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = None if shared else jax.random.normal(ks[1], (B, H, N, dh))
    v = jax.random.normal(ks[2], (B, H, N, dh))
    qi = jnp.sort(jax.random.randint(ks[3], (B, H, kc, w), 0, N), axis=-1)
    ki = None if shared else jnp.sort(
        jax.random.randint(ks[4], (B, H, kc, w), 0, N), axis=-1)
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    kvalid = jax.random.bernoulli(ks[5], 0.9, (B, N)) if valid else None
    of = ops.routed_attention_fused(q, k, v, qi, ki, pos, causal=causal,
                                    kvalid=kvalid, bq=bq, bk=bk)
    ref = _fused_reference(q, k, v, qi, ki, pos, causal, kvalid)
    assert float(jnp.abs(of - ref).max()) < TOL["float32"]


def _unique_members(key, B, H, N, kc):
    """(B,H,k,N/k) sorted memberships that partition the sequence, as
    balanced top-k gives them."""
    perms = [jax.random.permutation(k, N)
             for k in jax.random.split(key, B * H)]
    return jnp.sort(jnp.stack(perms).reshape(B, H, kc, N // kc), axis=-1)


def _spy_lse(monkeypatch, ra, name, rec):
    """Record the row stats (the second output) of ``ra.<name>`` each time
    the compiled program runs."""
    orig = getattr(ra, name)
    jax.clear_caches()      # no earlier trace may skip the spy

    def spy(*a, **kw):
        out = orig(*a, **kw)
        jax.debug.callback(lambda x: rec.__setitem__(name, x), out[1])
        return out
    monkeypatch.setattr(ra, name, spy)


@pytest.mark.parametrize("members,causal,valid", [
    ("unique", True, False), ("unique", True, True),
    ("repeated", True, False), ("unique", False, True),
])
def test_fused_forward_and_lse_bitwise_at_twelve_tiles(monkeypatch, members,
                                                       causal, valid):
    """w/bq = 12, the rt-imagenet64 cell's sub-tiles a cluster side (w 1536
    at 128): with unique sorted members at increasing positions the
    causal kernel visits the band alone (78 of 144 pairs), with repeated
    members every pair. The band's output and lse equal, bit for bit,
    the same kernel's with every cluster's band flag down (every pair
    visited)."""
    import repro.kernels.routing_attention as ra
    B, H, N, dh, kc, w, b = 1, 2, 768, 32, 4, 192, 16
    ks = jax.random.split(KEY, 6)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    v = jax.random.normal(ks[2], (B, H, N, dh))
    qi = (_unique_members(ks[3], B, H, N, kc) if members == "unique" else
          jnp.sort(jax.random.randint(ks[3], (B, H, kc, w), 0, N), axis=-1))
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    kvalid = jax.random.bernoulli(ks[5], 0.9, (B, N)) if valid else None
    pq = _seq_at(pos, qi)
    band = ra.band_clusters(pq, pq, causal, b, b)
    assert bool(band.all()) == (causal and members == "unique")
    rec = {}
    _spy_lse(monkeypatch, ra, "_fused_fwd_call", rec)

    def fused():
        o = ops.routed_attention_fused(q, None if causal else q, v, qi,
                                       None if causal else qi, pos,
                                       causal=causal, kvalid=kvalid, bq=b,
                                       bk=b)
        jax.block_until_ready(o)
        jax.effects_barrier()
        return o, rec.pop("_fused_fwd_call").reshape(-1)

    o_band, lse_band = fused()
    monkeypatch.setattr(ra, "band_clusters", lambda pq, pk, causal, bq, bk:
                        jnp.zeros(pq.shape[:-1], bool))
    jax.clear_caches()
    o_every, lse_every = fused()
    assert bool(jnp.array_equal(o_band, o_every)), \
        float(jnp.abs(o_band - o_every).max())
    assert bool(jnp.array_equal(lse_band, lse_every))


def _band_case(case, B, H, N, kc):
    """(q_idx, k_idx or None, positions, kvalid, causal) of a named input
    regime for the fused kernel's band choice."""
    w = N // kc
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    arange = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    members = _unique_members(ks[0], B, H, N, kc)
    contiguous = jnp.broadcast_to(jnp.arange(N).reshape(kc, w), (B, H, kc, w))
    if case == "band":
        return members, None, arange, None, True
    if case == "doc_resets":          # positions restart every 48 tokens
        return members, None, arange % 48, None, True
    if case == "padded_key_tile":     # cluster 1's first key sub-tile
        kvalid = jnp.broadcast_to((jnp.arange(N) < w)
                                  | (jnp.arange(N) >= w + 16), (B, N))
        return contiguous, contiguous, arange, kvalid, True
    if case == "dead_query_tile":     # keys all after cluster 0's queries
        return contiguous, jnp.roll(contiguous, -1, axis=2), arange, None, \
            True
    if case == "noncausal_padded_tile":
        kvalid = jnp.broadcast_to(jnp.arange(N) >= 16, (B, N))
        return contiguous, contiguous, arange, kvalid, False
    raise ValueError(case)


@pytest.mark.parametrize("case", ["band", "doc_resets", "padded_key_tile",
                                  "dead_query_tile", "noncausal_padded_tile"])
def test_fused_grad_parity_across_band_choices(case):
    """Fused output and VJP vs the XLA reference on clusters that take the
    causal band and on clusters that must visit every pair: positions
    that restart, a wholly padded key sub-tile, a query sub-tile with no
    attendable key, non-causal padding."""
    import repro.kernels.routing_attention as ra
    from repro.core.routing import _block_attention
    B, H, N, dh, kc, b = 1, 2, 256, 32, 4, 16
    w = N // kc
    qi, ki, pos, kvalid, causal = _band_case(case, B, H, N, kc)
    shared = ki is None
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = None if shared else jax.random.normal(ks[1], (B, H, N, dh))
    v = jax.random.normal(ks[2], (B, H, N, dh))
    wt = jax.random.normal(ks[3], (B, H, kc, w, dh))
    kidx = qi if shared else ki

    def rows(x, idx):
        return jnp.take_along_axis(x, idx.reshape(B, H, -1, 1),
                                   axis=2).reshape(B, H, kc, w, dh)

    def seqg(x, idx):
        return jnp.take_along_axis(jnp.broadcast_to(x[:, None], (B, H, N)),
                                   idx.reshape(B, H, -1),
                                   axis=2).reshape(B, H, kc, w)

    pq, pk = seqg(pos, qi), seqg(pos, kidx)
    vk = None if kvalid is None else seqg(kvalid, kidx)
    band = ra.band_clusters(pq, jnp.where(vk, pk, ra.SENTINEL)
                            if vk is not None else pk, causal, b, b)
    every, some = bool(band.all()), bool(band.any())
    assert {"band": every, "doc_resets": not every,
            "padded_key_tile": every, "dead_query_tile": some and not every,
            "noncausal_padded_tile": not some}[case], band

    def fused(q, k, v):
        return (ops.routed_attention_fused(q, k, v, qi, ki, pos,
                                           causal=causal, kvalid=kvalid,
                                           bq=b, bk=b) * wt).sum()

    def xla(q, k, v):
        kk = q if k is None else k
        og, _ = _block_attention(rows(q, qi), rows(kk, kidx),
                                 rows(v, kidx), pq, pk, causal, vk, False)
        return (og * wt).sum()

    args = (0, 2) if shared else (0, 1, 2)
    f, g = jax.value_and_grad(fused, argnums=args)(q, k, v)
    fr, gr = jax.value_and_grad(xla, argnums=args)(q, k, v)
    assert abs(float(f - fr)) < 1e-3 * max(1.0, abs(float(fr)))
    assert _grad_maxdiff(g, gr) < GRAD_TOL
    if case == "dead_query_tile":     # no attendable key: zero gradient
        assert float(jnp.abs(g[0][:, :, :w]).max()) == 0.0


# ---------------------------------------------------------------------------
# Paged fused kernel: double-buffered sequence-plane DMA (the VMEM pager)
# ---------------------------------------------------------------------------
def _fused_inputs(B, H, N, dh, kc, w, *, shared, valid, key=KEY):
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    k = None if shared else jax.random.normal(ks[1], (B, H, N, dh))
    qi = jnp.sort(jax.random.randint(ks[3], (B, H, kc, w), 0, N), axis=-1)
    ki = qi if shared else jnp.sort(
        jax.random.randint(ks[4], (B, H, kc, w), 0, N), axis=-1)
    v = jax.random.normal(ks[2], (B, H, N, dh))
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    kvalid = jax.random.bernoulli(ks[5], 0.9, (B, N)) if valid else None
    return q, k, v, qi, ki, pos, kvalid


@pytest.mark.parametrize("shared,causal,valid", [
    (True, True, False), (True, True, True),
    (False, False, False), (False, True, True),
])
def test_paged_fused_matches_unpaged_bitwise(shared, causal, valid):
    """The paged memory plan changes how rows reach VMEM (per-row DMA vs
    whole-plane residency), not what is computed on them: forward output
    must be bit-identical to the unpaged kernel."""
    B, H, N, dh, kc, w = 2, 2, 512, 32, 2, 256
    q, k, v, qi, ki, pos, kvalid = _fused_inputs(B, H, N, dh, kc, w,
                                                 shared=shared, valid=valid)
    up = ops.routed_attention_fused(q, k, v, qi, ki, pos, causal=causal,
                                    kvalid=kvalid, paged=False)
    pg = ops.routed_attention_fused(q, k, v, qi, ki, pos, causal=causal,
                                    kvalid=kvalid, paged=True)
    assert bool(jnp.array_equal(up, pg)), float(jnp.abs(up - pg).max())


@pytest.mark.parametrize("w", [64, 256, 384])
def test_paged_double_buffer_chunk_counts(w):
    """One, two and an odd number of sub-tiles per cluster (bq = bk =
    min(128, w): 1, 2, 3 for w = 64, 256, 384), over three clusters a
    batch·head and two batch·heads: cluster c+1 is prefetched into the
    slot c did not use, the third cluster lands back in slot 0, and each
    batch·head starts afresh. Forward bitwise between the memory plans;
    both plans' VJPs within GRAD_TOL of the XLA reference."""
    from repro.core.routing import _block_attention
    B, H, N, dh, kc = 1, 2, 768, 32, 3
    q, _, v, qi, _, pos, _ = _fused_inputs(B, H, N, dh, kc, w,
                                           shared=True, valid=False)
    wt = jax.random.normal(jax.random.PRNGKey(7), (B, H, kc, w, dh))

    def gath(x):
        return jnp.take_along_axis(x, qi.reshape(B, H, -1, 1),
                                   axis=2).reshape(B, H, kc, w, -1)

    pq = gath(jnp.broadcast_to(pos[:, None, :, None], (B, H, N, 1)))[..., 0]

    def loss(paged):
        if paged is None:
            return lambda q, v: (_block_attention(
                gath(q), gath(q), gath(v), pq, pq, True, None, False)[0]
                * wt).sum()
        return lambda q, v: (ops.routed_attention_fused(
            q, None, v, qi, None, pos, causal=True, paged=paged) * wt).sum()

    up = ops.routed_attention_fused(q, None, v, qi, None, pos, causal=True,
                                    paged=False)
    pg = ops.routed_attention_fused(q, None, v, qi, None, pos, causal=True,
                                    paged=True)
    assert bool(jnp.array_equal(up, pg))
    gr = jax.grad(loss(None), argnums=(0, 1))(q, v)
    for paged in (False, True):
        g = jax.grad(loss(paged), argnums=(0, 1))(q, v)
        assert _grad_maxdiff(g, gr) < GRAD_TOL, paged


@pytest.mark.parametrize("case", ["causal_shared", "padded",
                                  "noncausal_separate", "causal_separate",
                                  "segmented"])
def test_paged_fused_beyond_cliff_parity(case, monkeypatch, paged_plan):
    """The paged plan at a paper-scale N (8448 = 33 clusters of 256, dh
    128): forward and gradient parity vs the XLA reference through the
    full routing module, across mask regimes. The auto plan here is
    resident for shared QK (two planes, 17.3 MB of the 24 MiB budget) and
    paged for separate keys; the fixture pages every call, and each
    kernel call's plan is recorded."""
    import repro.kernels.routing_attention as ra
    from repro.configs.base import RoutingConfig
    from repro.core.kmeans import init_kmeans
    from repro.core.routing import routed_attention
    B, H, N, dh, kc = 1, 1, 8448, 128, 33
    plans = []
    auto = ra.fused_paged_default
    monkeypatch.setattr(ra, "fused_paged_default",
                        lambda *a: plans.append(auto(*a)) or plans[-1])
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    v = jax.random.normal(ks[1], (B, H, N, dh))
    wt = jax.random.normal(ks[3], (B, H, N, dh))
    st = init_kmeans(ks[2], H, kc, dh)
    pm = (jnp.broadcast_to(jnp.arange(N)[None, :] < N - 300, (B, N))
          if case == "padded" else None)
    if case in ("noncausal_separate", "causal_separate"):
        cfg = RoutingConfig(num_clusters=kc, causal=case == "causal_separate",
                            share_qk=False)
        k = jax.random.normal(jax.random.PRNGKey(11), (B, H, N, dh))
    else:
        cfg = RoutingConfig(num_clusters=kc,
                            segments=2 if case == "segmented" else 1)
        k = None

    def loss(impl):
        def f(q, k, v):
            out = routed_attention(q, k, v, st, cfg, pad_mask=pm,
                                   update_state=False, impl=impl).out
            return (out * wt).sum()
        return f

    args = (0, 2) if k is None else (0, 1, 2)
    o = routed_attention(q, k, v, st, cfg, pad_mask=pm,
                         update_state=False, impl="pallas_fused").out
    orf = routed_attention(q, k, v, st, cfg, pad_mask=pm,
                           update_state=False, impl="xla").out
    assert float(jnp.abs(o - orf).max()) < TOL["float32"]
    g = jax.grad(loss("pallas_fused"), argnums=args)(q, k, v)
    gr = jax.grad(loss("xla"), argnums=args)(q, k, v)
    assert _grad_maxdiff(g, gr) < GRAD_TOL
    assert plans and all(plans), plans


def _spy_paged_grid_specs(monkeypatch, calls):
    """Route pl.pallas_call through a spy that records the in_specs and
    scratch_shapes of every paged kernel build (q/k/v left in HBM: ANY
    memory-space operands)."""
    import types

    import repro.kernels.routing_attention as ra
    orig = ra.pl.pallas_call

    def spy(kernel, *a, **kw):
        specs = kw.get("in_specs") or []
        if any(getattr(sp, "memory_space", None) == ra.pl.ANY
               for sp in specs):
            calls.append(types.SimpleNamespace(
                in_specs=specs, scratch_shapes=kw["scratch_shapes"]))
        return orig(kernel, *a, **kw)

    monkeypatch.setattr(ra.pl, "pallas_call", spy)


def _scratch_shapes(grid_spec):
    return [(type(s).__name__,) + tuple(getattr(s, "shape", ()))
            for s in grid_spec.scratch_shapes]


def test_paged_vmem_scratch_independent_of_seq_len(monkeypatch):
    """Structural VMEM bound: the paged kernels' scratch allocations
    (the cluster's row buffers + DMA semaphores) are functions of (w,
    dh) only — identical between N and 4N — and the q/k/v operands stay
    in ANY memory space (no N-sized VMEM window in any BlockSpec)."""
    calls = []
    _spy_paged_grid_specs(monkeypatch, calls)

    def build(n):
        kc = n // 128
        q, _, v, qi, ki, pos, _ = _fused_inputs(1, 1, n, 64, kc, 128,
                                                shared=True, valid=False)

        def loss(q, v):
            return (ops.routed_attention_fused(q, None, v, qi, ki, pos,
                                               causal=True, paged=True)
                    ** 2).sum()

        jax.grad(loss, argnums=(0, 1))(q, v)
        got, calls[:] = list(calls), []
        return got

    small, big = build(256), build(1024)
    # forward (x2: once for the value path, once inside the VJP), and the
    # one backward kernel
    assert len(small) == len(big) and len(big) >= 3
    for gs_s, gs_b in zip(small, big):
        assert _scratch_shapes(gs_s) == _scratch_shapes(gs_b)
        for name, *shape in _scratch_shapes(gs_b):
            assert 1024 not in shape, (name, shape)
        anys = [sp for sp in gs_b.in_specs
                if getattr(sp, "block_shape", None) is None]
        assert len(anys) >= 2    # q and v (k aliases q: shared-QK case)


def test_fused_auto_pages_past_residency_budget(monkeypatch):
    """paged=None switches memory plan on the resident planes' bytes —
    rt-enwik8's N=8192, dh=128 stays resident with three planes (exactly
    at the budget), one more head-dim column pages, and so does N=16384
    with two — and the switch structurally reaches the DMA kernel."""
    import repro.kernels.routing_attention as ra
    from repro.kernels import common
    assert common.fused_paged_default(8192, 128, 3) is False
    assert common.fused_paged_default(8192, 129, 3) is True
    assert common.fused_paged_default(12288, 128, 2) is False
    assert common.fused_paged_default(16384, 128, 2) is True
    assert common.fused_paged_default(64, 64, 2, paged=True) is True
    assert common.fused_paged_default(1 << 20, 128, 2, paged=False) is False

    calls = []
    _spy_paged_grid_specs(monkeypatch, calls)
    monkeypatch.setattr(common, "FUSED_RESIDENT_BYTES", 1024)
    q, _, v, qi, ki, pos, _ = _fused_inputs(1, 1, 256, 32, 2, 128,
                                            shared=True, valid=False)
    # bypass the jit wrapper: its trace cache keys on shapes, not on the
    # monkeypatched budget
    ra.routed_attention_fused(q, None, v, qi, ki, pos, causal=True,
                              interpret=True)
    assert calls, "paged=None did not route past the shrunk budget"


@pytest.mark.parametrize("arch,n,paged", [
    ("rt-enwik8", 8192, False),        # 16 MiB of planes
    ("rt-imagenet64", 12288, False),   # 24 MiB: exactly the budget
    ("rt-enwik8", 16384, True),        # 32 MiB: past it
])
def test_fused_plan_at_cell_shapes(arch, n, paged):
    """The plan the benchmark cells were measured on: shared-QK planes at
    rt-enwik8's dh 128 and N 8192 stay resident, so do rt-imagenet64's
    at dh 64 (128 lanes) and N 12288, at the budget exactly; twice
    rt-enwik8's sequence pages."""
    from repro.configs import get_config
    from repro.kernels import common
    dh = get_config(arch).head_dim_
    planes = common.fused_resident_bytes(n, dh, 2)
    assert common.fused_paged_default(n, dh, 2) is paged
    assert (planes > common.FUSED_RESIDENT_BYTES) is paged
    if arch == "rt-imagenet64":
        assert planes == common.FUSED_RESIDENT_BYTES


def test_fused_refuses_cluster_past_vmem_budget():
    """A cluster is held whole in VMEM, so its size w = N/k is bounded:
    rt-enwik8's w = 256 and N = 32768 in 32 clusters (w = 1024, separate
    keys) fit FUSED_CLUSTER_BYTES, w = 2048 is refused before tracing."""
    import repro.kernels.routing_attention as ra
    from repro.kernels import common
    assert common.fused_cluster_bytes(256, 128, 2) == 1581056
    assert common.fused_cluster_bytes(1024, 128, 3) <= \
        common.FUSED_CLUSTER_BYTES
    q, _, v, qi, _, pos, _ = _fused_inputs(1, 1, 2048, 128, 1, 2048,
                                           shared=True, valid=False)
    with pytest.raises(ValueError, match="FUSED_CLUSTER_BYTES"):
        ra.routed_attention_fused(q, None, v, qi, None, pos, interpret=True)


def test_interpret_default_derived_from_platform(monkeypatch):
    from repro.kernels import common
    assert common.default_interpret(None) == (jax.default_backend()
                                              != "tpu")
    assert common.default_interpret(True) is True
    assert common.default_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.default_interpret(None) is False


# ---------------------------------------------------------------------------
# Train path: the fused kernel is legal under jax.grad end to end
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plan", ["resident", "paged"])
def test_train_step_on_pallas_kernels_decreases_loss(plan, request):
    """make_train_step(impl="pallas_fused") runs a 20-step loss-decreasing
    fit with the Pallas kernel on the train path (interpret mode on CPU),
    in either memory plan — no silent fallback to the XLA reference."""
    from repro.configs.base import (ModelConfig, RoutingConfig, RunConfig,
                                    TrainConfig)
    from repro.data.synthetic import SyntheticLoader
    from repro.train.train_step import init_train_state, make_train_step
    cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=4, d_ff=128, vocab_size=64,
                      attention="routing",
                      routing=RoutingConfig(num_clusters=4),
                      dtype="float32")
    run = RunConfig(model=cfg, train=TrainConfig(
        global_batch=8, seq_len=64, steps=20, lr=3e-3, schedule="const",
        warmup_steps=5, remat="none"))
    if plan == "paged":
        request.getfixturevalue("paged_plan")
    ts = init_train_state(run, KEY)
    step = jax.jit(make_train_step(run, impl="pallas_fused"))
    loader = SyntheticLoader("markov", cfg.vocab_size, 8, 64)
    losses = []
    for _, b in zip(range(run.train.steps), loader):
        ts, m = step(ts, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
