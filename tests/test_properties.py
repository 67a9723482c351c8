"""Hypothesis property tests on system invariants."""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dep: pip install -e .[property]")
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.kmeans import cluster_scores, init_kmeans, normalize_routing
from repro.core.routing import balanced_topk

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")


@given(n=st.integers(8, 64), k=st.integers(1, 8), seed=st.integers(0, 99))
def test_balanced_topk_invariants(n, k, seed):
    """Indices sorted ascending, in range, exactly w per centroid, unique."""
    w = max(1, n // k)
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.randn(1, 1, n, k))
    idx = np.asarray(balanced_topk(scores, w))
    assert idx.shape == (1, 1, k, w)
    assert (idx >= 0).all() and (idx < n).all()
    assert (np.diff(idx, axis=-1) > 0).all()        # sorted & unique


@given(seed=st.integers(0, 99), d=st.sampled_from([8, 16, 32]))
def test_normalized_vectors_argmax_is_nearest(seed, d):
    """On the (scaled) unit ball, argmax dot == argmin euclidean distance
    (the MIPS <-> NNS equivalence, paper eq. 10-12)."""
    rng = np.random.RandomState(seed)
    r = normalize_routing(jnp.asarray(rng.randn(1, 1, 16, d)))
    mu = normalize_routing(jnp.asarray(rng.randn(1, 1, 4, d)))[0, 0]
    mu = mu[None]                                    # (1,4,d) same norm
    s = cluster_scores(r, mu)
    by_dot = np.asarray(jnp.argmax(s, -1))[0, 0]
    dists = np.linalg.norm(np.asarray(r)[0, 0][:, None]
                           - np.asarray(mu)[0][None], axis=-1)
    by_dist = dists.argmin(-1)
    assert (by_dot == by_dist).all()


@given(seed=st.integers(0, 99), n=st.integers(2, 6))
def test_online_softmax_merge_associative(seed, n):
    """Flash (m, l, acc) merge over arbitrary chunkings == full softmax."""
    rng = np.random.RandomState(seed)
    logits = rng.randn(n * 8).astype(np.float32) * 3
    vals = rng.randn(n * 8, 4).astype(np.float32)
    full = (np.exp(logits - logits.max())
            / np.exp(logits - logits.max()).sum()) @ vals

    m, l, acc = -np.inf, 0.0, np.zeros(4)
    for c in range(n):
        sl = slice(c * 8, (c + 1) * 8)
        mc = logits[sl].max()
        m_new = max(m, mc)
        p = np.exp(logits[sl] - m_new)
        corr = np.exp(m - m_new) if np.isfinite(m) else 0.0
        l = l * corr + p.sum()
        acc = acc * corr + p @ vals[sl]
        m = m_new
    np.testing.assert_allclose(acc / l, full, rtol=1e-5, atol=1e-6)


@given(seed=st.integers(0, 99), scale=st.floats(1e-3, 1e3))
def test_int8_quantization_error_bound(seed, scale):
    """|x - dequant(quant(x))| <= amax(group) / 254 elementwise, for the
    group-wise int8 codes of the compressed gradient exchange."""
    _compression = pytest.importorskip(
        "repro.dist.compression", reason="repro.dist is not part of this build")
    group = 16
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(64).astype(np.float32) * scale)
    q, s = _compression._quantize(x, group)
    assert q.dtype == jnp.int8 and s.shape == (64 // group,)
    err = jnp.abs(x - _compression._dequantize(q, s, group))
    amax = jnp.max(jnp.abs(x.reshape(-1, group)), axis=-1)
    bound = jnp.repeat(amax / 254.0, group) * 1.01 + 1e-6
    assert bool((err <= bound).all())


@given(seed=st.integers(0, 49))
def test_routing_output_permutation_equivariance(seed):
    """Permuting batch rows permutes outputs (no cross-example leakage)."""
    from repro.configs.base import RoutingConfig
    from repro.core.routing import routed_attention
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(3, 2, 32, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(3, 2, 32, 8).astype(np.float32))
    stt = init_kmeans(jax.random.PRNGKey(seed), 2, 4, 8)
    cfg = RoutingConfig(num_clusters=4)
    out = routed_attention(q, None, v, stt, cfg).out
    perm = jnp.array([2, 0, 1])
    out_p = routed_attention(q[perm], None, v[perm], stt, cfg).out
    assert float(jnp.abs(out[perm] - out_p).max()) < 1e-5


@given(seed=st.integers(0, 49), w=st.sampled_from([8, 16]))
def test_local_attention_receptive_field(seed, w):
    """Output at position i depends only on inputs in blocks b-1, b."""
    from repro.core.local import local_attention
    rng = np.random.RandomState(seed)
    N = 64
    q = jnp.asarray(rng.randn(1, 1, N, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 1, N, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 1, N, 8).astype(np.float32))
    o1 = local_attention(q, k, v, window=w, causal=True)
    i = N - 1                                   # last token, block b
    lo = (i // w - 1) * w                       # start of block b-1
    # perturb everything strictly before lo: output at i must not change
    k2 = k.at[:, :, :lo].set(0.0)
    v2 = v.at[:, :, :lo].set(0.0)
    o2 = local_attention(q, k2, v2, window=w, causal=True)
    assert float(jnp.abs(o1[:, :, i] - o2[:, :, i]).max()) < 1e-5


@given(vocab=st.sampled_from([32, 64]), seed=st.integers(0, 20))
def test_lm_loss_uniform_logits(vocab, seed):
    """Uniform logits -> loss == log(vocab)."""
    from repro.models.model import lm_loss
    rng = np.random.RandomState(seed)
    logits = jnp.zeros((2, 8, vocab))
    targets = jnp.asarray(rng.randint(0, vocab, (2, 8)))
    loss, _ = lm_loss(logits, targets)
    assert abs(float(loss) - np.log(vocab)) < 1e-5
