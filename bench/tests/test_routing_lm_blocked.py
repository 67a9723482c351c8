"""The blocked reference (``refs/routing_lm_blocked.py``) against the
whole-tensor one (``refs/routing_lm.py``) at a small size: the same
losses, step 1's gradient norms and the weights' change over three steps,
and the same local and routing attention outputs."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.refs import routing_lm, routing_lm_blocked

CONFIG = Path(__file__).resolve().parents[1] / "configs" / \
    "rt-imagenet64-train.json"


def small():
    c = json.loads(CONFIG.read_text())
    m = dict(c["model"], num_layers=2, d_model=128, num_heads=4,
             routing_heads=2, head_dim=64, d_ff=256, num_clusters=4,
             local_window=64, max_seq_len=256)
    return m, c["train"]


def test_attention_blocks_equal_whole():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v = (jax.random.normal(kk, (2, 3, 256, 64)) for kk in ks[:3])
    mu = jax.random.normal(ks[3], (3, 4, 64))
    np.testing.assert_allclose(
        routing_lm_blocked.local_attention(q, k, v, 64),
        routing_lm.local_attention(q, k, v, 64), rtol=1e-5, atol=1e-5)
    for got, want in zip(routing_lm_blocked.routing_attention(q, v, mu, 4),
                         routing_lm.routing_attention(q, v, mu, 4)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_train_equals_whole_tensor_reference(dropout):
    c, t = small()
    c["dropout"] = dropout
    key = routing_lm.seed_key(2 ** 33 + 17)
    from bench.traffic.kinds.markov_pool import markov_rows
    toks = markov_rows(jax.random.PRNGKey(3), rows=6, length=257, vocab=256,
                       scale=2.0)
    batches = [toks[i * 2:(i + 1) * 2] for i in range(3)]
    want = routing_lm.train(key, batches, c, t, 0)
    got = routing_lm_blocked.train(key, batches, c, t, 0)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1:], want[1:]):
        assert sorted(g) == sorted(w)
        for n in w:
            np.testing.assert_allclose(g[n], w[n], rtol=1e-4, atol=1e-7,
                                       err_msg=n)
