"""bench/counts.py against rt-enwik8 counts worked by hand."""
import json
from pathlib import Path

import pytest

from bench import counts

C = json.loads((Path(__file__).parents[1] / "configs"
                / "rt-enwik8-train.json").read_text())["model"]
N = 8192


def test_param_count():
    # embeddings 2*256*1024 + final norm 2*1024 + 12 layers of
    # 4*1024^2 (q,k,v,o) + 2*1024*4096 (MLP) + 4*1024 (two norms)
    per_layer = 4 * 1024 ** 2 + 2 * 1024 * 4096 + 4 * 1024
    assert counts.param_count(C) == 2 * 256 * 1024 + 2048 + 12 * per_layer
    assert counts.param_count(C) == 151_570_432
    assert 6 * counts.param_count(C) == pytest.approx(909e6, rel=1e-3)


def test_pairs_per_token():
    # 32 blocks of 256: own block 256*257/2 each, previous block 256^2
    # for 31 of them
    assert counts.local_pairs_per_token(N, 256) == (
        (256 * 257 / 2 * 32 + 256 ** 2 * 31) / N) == 376.5
    # 32 clusters of 256 members, causal inside each
    assert counts.routing_pairs_per_token(N, 32) == 128.5


def test_attention_flops_per_token():
    fwd_layer = 4 * 128 * (4 * 376.5 + 4 * 128.5)       # 1,034,240
    affinity = 2 * 32 * 128 * 4                        # 32,768
    want = 12 * (3 * fwd_layer + affinity)             # 37,625,856
    assert counts.attention_flops_per_token(C, N) == want
    assert want == pytest.approx(38e6, rel=0.02)
    assert counts.train_flops_per_token(C, N) == 6 * 151_570_432 + want


def test_routing_kernel_counts_one_call():
    sub = dict(C, num_layers=1, routing_heads=1)
    flops, nbytes = counts.routing_kernel_counts(sub, N, 1, 4)
    assert flops == 3 * 4 * 128 * (32 * 256 * 257 / 2)
    plane = N * 128 * 4
    blocks = 32 * 256 * 128 * 4
    rows = 32 * 256 * 4
    assert nbytes == (2 * plane + blocks + 2 * rows) + (
        4 * plane + 2 * blocks + 2 * rows)
    # calls scale linearly in rows, layers and routing heads
    f4, b4 = counts.routing_kernel_counts(C, N, 4, 4)
    assert (f4, b4) == (flops * 4 * 12 * 4, nbytes * 4 * 12 * 4)
