"""Paged-decode kernel (routing/pallas_paged) parity + resolution.

The contract under test (kernels/routing_decode.py):

* cache trajectories are BIT-identical to the xla cluster-page decode
  (the paged backend runs the reference's routing + cache-write code);
* greedy token streams are bit-identical over long multi-step decode
  (the only cross-step state is the cache and the argmax token);
* per-step attention outputs / model logits agree to float ulps (exact
  bitwise equality of f32 reductions across differently-compiled
  programs is compiler-dependent — see the kernel docstring);
* garbage in beyond-min(rlen,cap) page slots cannot leak;
* TPU auto-resolution (also when a CPU host resolves for "tpu" and runs
  the kernels in interpret mode) picks pallas_paged for decode while
  apply stays on pallas_fused.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import attn
from repro.attn import registry
from repro.attn.spec import AttentionSpec
from repro.configs.base import ModelConfig, RoutingConfig as MRoutingConfig
from repro.core.routing import RoutingConfig
from repro.models.model import init_model
from repro.serve.serving import init_cache, make_serve_step, prefill

KEY = jax.random.PRNGKey(0)


def _spec(variant, H=4, dh=64, kc=8, cap=16, window=16):
    rc = RoutingConfig(num_clusters=kc, window=cap)
    if variant == "routing":
        return AttentionSpec(variant="routing", num_heads=H, num_kv_heads=H,
                             head_dim=dh, routing=rc)
    return AttentionSpec(variant="local+routing", num_heads=H,
                         num_kv_heads=H, head_dim=dh, window=window,
                         routing=rc, routing_heads=H // 2)


def _mu(spec, key):
    Hr = (attn.head_split(spec)[1] if spec.variant == "local+routing"
          else spec.num_heads)
    mu = jax.random.normal(key, (Hr, spec.routing.num_clusters,
                                 spec.head_dim), jnp.float32)
    return mu / jnp.linalg.norm(mu, axis=-1, keepdims=True)


def _resolve_for_tpu(monkeypatch):
    """Make attention resolution default to the TPU backends on this
    host; the kernels still run in interpret mode, since that derives
    from the backend the program really runs on."""
    monkeypatch.setattr(attn, "_platform",
                        lambda platform=None: platform or "tpu")


def _tree_bitwise(a, b):
    return all(bool((x == y).all())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("variant", ["routing", "local+routing"])
def test_paged_decode_multi_step_parity(variant):
    """80 decode steps: caches bitwise-equal every step, outputs within
    float ulps, and a fixed linear readout's argmax 'tokens' identical."""
    spec = _spec(variant)
    B, H, dh = 2, spec.num_heads, spec.head_dim
    key = jax.random.PRNGKey(1)
    mu = _mu(spec, key)
    readout = jax.random.normal(jax.random.PRNGKey(2), (H * dh, 256),
                                jnp.float32)
    cache_x = attn.init_decode_cache(spec, B, 256, jnp.float32)
    cache_p = jax.tree.map(lambda x: x, cache_x)
    for t in range(80):
        k1, k2, key = jax.random.split(key, 3)
        q = jax.random.normal(k1, (B, H, 1, dh), jnp.float32)
        v = jax.random.normal(k2, (B, H, 1, dh), jnp.float32)
        pos = jnp.full((B,), t, jnp.int32)
        ox = attn.attend(spec, q, q, v, state=mu, cache=cache_x, pos=pos,
                         impl="xla")
        op = attn.attend(spec, q, q, v, state=mu, cache=cache_p, pos=pos,
                         impl="pallas_paged")
        cache_x, cache_p = ox.cache, op.cache
        assert _tree_bitwise(cache_x, cache_p), f"cache diverged at t={t}"
        d = float(jnp.abs(ox.out - op.out).max())
        assert d <= 1e-5, f"attention out drift {d} at t={t}"
        tok_x = jnp.argmax(ox.out.reshape(B, -1) @ readout, -1)
        tok_p = jnp.argmax(op.out.reshape(B, -1) @ readout, -1)
        assert bool((tok_x == tok_p).all()), f"token flip at t={t}"


@pytest.mark.parametrize("variant", ["routing", "local+routing"])
def test_paged_decode_poisoned_slots_no_leak(variant):
    """Beyond-min(rlen,cap) page slots hold garbage after ring wraps and
    compactions; neither decode path may let it reach the output. Poison
    them with 1e30 (finite, so a leak cannot hide behind NaN*0) and
    demand the poisoned run equals the clean run bit for bit."""
    spec = _spec(variant)
    B, H, dh = 2, spec.num_heads, spec.head_dim
    key = jax.random.PRNGKey(3)
    mu = _mu(spec, key)
    cache = attn.init_decode_cache(spec, B, 256, jnp.float32)
    for t in range(10):          # partially fill: many slots unoccupied
        k1, k2, key = jax.random.split(key, 3)
        q = jax.random.normal(k1, (B, H, 1, dh), jnp.float32)
        v = jax.random.normal(k2, (B, H, 1, dh), jnp.float32)
        cache = attn.attend(spec, q, q, v, state=mu, cache=cache,
                            pos=jnp.full((B,), t, jnp.int32),
                            impl="xla").cache
    cap = cache["rk"].shape[3]
    occ = jnp.minimum(cache["rlen"], cap)[..., None, None]     # (B,Hr,kc,1,1)
    dead = jnp.arange(cap)[None, None, None, :, None] >= occ
    poisoned = dict(cache)
    poisoned["rk"] = jnp.where(dead, 1e30, cache["rk"])
    poisoned["rv"] = jnp.where(dead, 1e30, cache["rv"])
    q = jax.random.normal(jax.random.PRNGKey(4), (B, H, 1, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(5), (B, H, 1, dh), jnp.float32)
    pos = jnp.full((B,), 10, jnp.int32)
    for impl in ("xla", "pallas_paged"):
        o_clean = attn.attend(spec, q, q, v, state=mu, cache=cache,
                              pos=pos, impl=impl)
        o_dirty = attn.attend(spec, q, q, v, state=mu, cache=poisoned,
                              pos=pos, impl=impl)
        assert bool(jnp.isfinite(o_dirty.out).all()), impl
        assert bool((o_clean.out == o_dirty.out).all()), \
            f"{impl}: poisoned slots leaked into the output"


@pytest.mark.parametrize("variant", ["routing", "local+routing"])
def test_decode_resolution_prefers_paged_on_tpu(variant):
    spec = _spec(variant)
    assert attn.decode_backend(spec, platform="tpu").impl == "pallas_paged"
    assert attn.decode_backend(spec, platform="cpu").impl == "xla"
    # the priority-20 tie with pallas_fused breaks toward fused for apply
    # (registration order); paged only owns decode
    assert registry.resolve(spec, needs_grad=True,
                            platform="tpu").impl == "pallas_fused"
    # same cluster-page cache layout on both decode paths: engines can
    # prefill under one impl and decode under the other
    assert (attn.decode_backend(spec, platform="tpu").layout.name
            == attn.decode_backend(spec, platform="cpu").layout.name)


def test_decode_resolution_mesh_falls_back_to_xla():
    """Like every Pallas backend, pallas_paged declares supports_mesh=
    False: decode under a GSPMD mesh resolves to the reference."""
    class FakeMesh:            # resolve() only reads .size
        size = 2
    spec = _spec("routing")
    assert attn.decode_backend(spec, mesh=FakeMesh(),
                               platform="tpu").impl == "xla"


def test_forced_interpret_env_resolution(monkeypatch):
    """Resolving for "tpu" on a CPU host routes auto resolution to the
    TPU backends, which run in interpret mode there."""
    from repro.kernels.common import default_interpret
    _resolve_for_tpu(monkeypatch)
    for variant in ("routing", "local+routing"):
        assert attn.decode_backend(_spec(variant)).impl == "pallas_paged"
    assert default_interpret(None) is (jax.default_backend() != "tpu")
    monkeypatch.undo()
    assert attn.decode_backend(_spec("routing")).impl == (
        "pallas_paged" if jax.default_backend() == "tpu" else "xla")


def test_model_decode_token_and_logit_parity(monkeypatch):
    """The acceptance gate: a real model decodes greedily for 24 steps
    under TPU resolution (pallas_paged decode, interpret mode off the
    chip) and under CPU resolution (xla decode) from the same prefill;
    token streams must match exactly, per-step vocab logits to ulps,
    and the cluster-page cache trajectories bit for bit."""
    cfg = ModelConfig(name="pd", family="dense", attention="local+routing",
                      routing=MRoutingConfig(num_clusters=4, local_window=16),
                      num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      d_ff=128, vocab_size=64, dtype="float32")
    params, kstate = init_model(cfg, KEY)
    B, TP, steps = 2, 32, 24
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, TP), 0,
                              cfg.vocab_size)
    cache = init_cache(cfg, B, max_len=TP + steps + 1)
    lg, cache = prefill(params, kstate, cache, {"tokens": toks}, cfg)
    cache_x = cache
    cache_p = jax.tree.map(lambda x: x, cache)

    tok_x = tok_p = lg[:, -1].argmax(-1).astype(jnp.int32)
    pos0 = jnp.full((B,), TP, jnp.int32)

    def compiled_step():
        # backends resolve while tracing: compile under each resolution
        return jax.jit(make_serve_step(cfg)).lower(
            params, kstate, cache, tok_x, pos0).compile()

    monkeypatch.setattr(attn, "_platform", lambda platform=None: "cpu")
    assert attn.decode_backend(
        attn.spec_for_layer(cfg, cfg.attention)).impl == "xla"
    step_xla = compiled_step()
    _resolve_for_tpu(monkeypatch)
    assert attn.decode_backend(
        attn.spec_for_layer(cfg, cfg.attention)).impl == "pallas_paged"
    step_paged = compiled_step()

    for t in range(TP, TP + steps):
        pos = jnp.full((B,), t, jnp.int32)
        lg_x, cache_x = step_xla(params, kstate, cache_x, tok_x, pos)
        lg_p, cache_p = step_paged(params, kstate, cache_p, tok_p, pos)
        d = float(jnp.abs(lg_x - lg_p).max())
        assert d <= 5e-4, f"vocab logit drift {d} at t={t}"
        tok_x = lg_x.argmax(-1).astype(jnp.int32)
        tok_p = lg_p.argmax(-1).astype(jnp.int32)
        assert bool((tok_x == tok_p).all()), f"greedy token flip at t={t}"
        for name in ("rk", "rv", "rlen"):
            a = [l[name] for l in jax.tree.leaves(
                cache_x, is_leaf=lambda x: isinstance(x, dict))
                if isinstance(l, dict) and name in l]
            b = [l[name] for l in jax.tree.leaves(
                cache_p, is_leaf=lambda x: isinstance(x, dict))
                if isinstance(l, dict) and name in l]
            assert all(bool((x == y).all()) for x, y in zip(a, b)), \
                f"page cache {name} diverged at t={t}"


def test_jitted_wrapper_names_the_kernel_and_keeps_its_work():
    """kernels.ops.paged_routing_decode: the same program as the bare
    kernel (bitwise equal output, the same compiled instructions) under
    the ``kernels/paged_routing_decode`` span."""
    import re

    from repro.kernels import ops
    from repro.kernels.routing_decode import paged_routing_decode
    B, Hr, kc, cap, dh = 2, 2, 4, 8, 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, Hr, dh))
    v = jax.random.normal(ks[1], (B, Hr, dh))
    rk = jax.random.normal(ks[2], (B, Hr, kc, cap, dh))
    rv = jax.random.normal(ks[3], (B, Hr, kc, cap, dh))
    rlen = jax.random.randint(ks[4], (B, Hr, kc), 0, 2 * cap)
    c = jnp.array([[0, 3], [2, 1]], jnp.int32)
    args = (r, v, rk, rv, rlen, c)
    bare = jax.jit(paged_routing_decode)
    wrapped = jax.jit(lambda *a: ops.paged_routing_decode(*a))
    assert bool(jnp.array_equal(bare(*args), wrapped(*args)))

    def work(fn):
        text = fn.lower(*args).compile().as_text()
        # the entry's instructions (its signature names the arguments)
        body = text[text.index("ENTRY"):].split("\n", 1)[1]
        return text, sorted(re.sub(r",? metadata=\{[^}]*\}", "",
                                   re.sub(r"%[\w.\-]+", "%", body))
                            .splitlines())
    text, w = work(wrapped)
    assert "kernels/paged_routing_decode" in text
    assert w == work(bare)[1]
