"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached: the chip's compiler runs here and refuses what the chip
would refuse (block tiling, VMEM and SMEM budgets, unaligned slices) —
faults interpret mode cannot show. Shapes are rt-enwik8's (configs/
paper.py): sequence 8192, head dim 128, 32 clusters of 256, window 256,
4 local + 4 routing heads; float32 as training runs, bfloat16 as serving
runs. Nothing executes; each compile takes a few seconds.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library,
and under pytest-xdist every worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import common

CFG = get_config("rt-enwik8")
DH = CFG.head_dim_
KC = CFG.routing.num_clusters
WINDOW = CFG.attn_window
HEADS = CFG.num_heads // 2              # per half of the local+routing split


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; the HLO must hold the kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _grad(fn, argnums):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=argnums)


@pytest.mark.parametrize("dtype,with_grad", [("float32", True),
                                             ("bfloat16", False)])
def test_local_kernel_compiles(one_chip, dtype, with_grad):
    from repro.kernels.local_attention import local_attention_kernel
    x = _shape(one_chip, (1, HEADS, 8192, DH), dtype)
    fn = lambda q, k, v: local_attention_kernel(q, k, v, WINDOW,
                                                interpret=False)
    _compile(_grad(fn, (0, 1, 2)) if with_grad else fn, x, x, x)


@pytest.mark.parametrize("n,dtype,with_grad,paged", [
    (8192, "float32", True, False),      # train: resident planes
    (16384, "float32", True, True),      # past the byte budget: paged
    (8192, "bfloat16", False, False),    # serve prefill
    (32768, "float32", True, True),      # w = 1024, the largest admitted
])
def test_fused_routing_kernel_compiles(one_chip, n, dtype, with_grad,
                                       paged):
    """One kernel a cluster forward, one for the whole backward."""
    from repro.kernels.routing_attention import routed_attention_fused
    # the auto plan (paged=None) is the one expected at this size
    assert common.fused_paged_default(n, DH, 2) is paged
    x = _shape(one_chip, (1, HEADS, n, DH), dtype)
    idx = _shape(one_chip, (1, HEADS, KC, n // KC), "int32")
    pos = _shape(one_chip, (1, n), "int32")

    def fn(q, v, idx, pos):
        return routed_attention_fused(q, None, v, idx, idx, pos,
                                      interpret=False)

    if with_grad:
        text = _compile(lambda q, v, i, p: _grad(
            lambda q, v: fn(q, v, i, p), (0, 1))(q, v), x, x, idx, pos)
    else:
        text = _compile(fn, x, x, idx, pos)
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls == (2 if with_grad else 1), calls


# rt-imagenet64 (configs/paper.py): 8 local + 8 routing heads of dh 64,
# window 2048, 8 clusters of 1536 over sequence 12288
IMG_N, IMG_DH, IMG_KC, IMG_WINDOW, IMG_HEADS = 12288, 64, 8, 2048, 8


def test_local_kernel_compiles_past_its_sub_tile(one_chip):
    """w = 2048: 128-row query sub-tiles against the key tiles the causal
    mask leaves (a one-shot (w x 2w) float32 score tile would be 32 MiB),
    with the gradient."""
    from repro.kernels.local_attention import (local_attention_kernel,
                                               sub_tile)
    assert sub_tile(IMG_WINDOW, IMG_DH) == 128
    x = _shape(one_chip, (1, IMG_HEADS, IMG_N, IMG_DH), "float32")
    fn = lambda q, k, v: local_attention_kernel(q, k, v, IMG_WINDOW,
                                                interpret=False)
    text = _compile(_grad(fn, (0, 1, 2)), x, x, x)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_local_kernel_compiles_past_its_sub_tile_encoder(one_chip):
    """Encoder mode at w = 2048: each sub-tile reads three whole (w, dh)
    key/value blocks (and dk/dv three query blocks) beside its score
    chunks, within the sub-tiled grid's scoped VMEM."""
    from repro.kernels.local_attention import local_attention_kernel
    x = _shape(one_chip, (1, IMG_HEADS, IMG_N, IMG_DH), "float32")
    fn = lambda q, k, v: local_attention_kernel(q, k, v, IMG_WINDOW,
                                                causal=False,
                                                interpret=False)
    text = _compile(_grad(fn, (0, 1, 2)), x, x, x)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("paged", [None, True])
def test_fused_routing_kernel_compiles_at_dh64(one_chip, paged):
    """dh = 64 rows padded to 128 lanes. The auto plan (None) at
    N = 12288 keeps the two (N, 128) planes resident (24 MiB, the
    budget); the paged plan compiles too, and a cluster of 1536 rows fits
    FUSED_CLUSTER_BYTES."""
    from repro.kernels.routing_attention import routed_attention_fused
    assert common.fused_paged_default(IMG_N, IMG_DH, 2) is False
    assert common.fused_cluster_bytes(IMG_N // IMG_KC, IMG_DH, 2) <= \
        common.FUSED_CLUSTER_BYTES
    x = _shape(one_chip, (1, IMG_HEADS, IMG_N, IMG_DH), "float32")
    idx = _shape(one_chip, (1, IMG_HEADS, IMG_KC, IMG_N // IMG_KC), "int32")
    pos = _shape(one_chip, (1, IMG_N), "int32")

    def fn(q, v, i, p):
        return _grad(lambda q, v: routed_attention_fused(
            q, None, v, i, i, p, interpret=False, paged=paged), (0, 1))(q, v)

    text = _compile(fn, x, x, idx, pos)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("paged", [None, True])
def test_fused_routing_forward_compiles_at_dh64(one_chip, paged):
    """The forward alone at the rt-imagenet64 shape (12 sub-tiles a
    cluster side): both unrolled bodies, the causal band and every pair,
    and the per-cluster band flag in SMEM fit the chip's budgets."""
    from repro.kernels.routing_attention import routed_attention_fused
    x = _shape(one_chip, (1, IMG_HEADS, IMG_N, IMG_DH), "float32")
    idx = _shape(one_chip, (1, IMG_HEADS, IMG_KC, IMG_N // IMG_KC), "int32")
    pos = _shape(one_chip, (1, IMG_N), "int32")

    def fn(q, v, i, p):
        return routed_attention_fused(q, None, v, i, None, p,
                                      interpret=False, paged=paged)

    text = _compile(fn, x, x, idx, pos)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_paged_decode_kernel_compiles(one_chip):
    from repro.kernels.routing_decode import paged_routing_decode
    B, cap, bf = 8, 256, "bfloat16"
    tok = _shape(one_chip, (B, HEADS, DH), bf)
    page = _shape(one_chip, (B, HEADS, KC, cap, DH), bf)
    _compile(lambda r, v, rk, rv, rl, c: paged_routing_decode(
        r, v, rk, rv, rl, c, interpret=False),
        tok, tok, page, page, _shape(one_chip, (B, HEADS, KC), "int32"),
        _shape(one_chip, (B, HEADS), "int32"))


def _dh_gather_ranks(text):
    """Ranks of every gather in compiled HLO whose result ends in the
    head dim (the signature of a gathered q/k/v copy)."""
    ranks = []
    for m in re.finditer(r"=\s*\w+\[([0-9,]*)\][^\n]*?\bgather\(", text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dims and dims[-1] == DH:
            ranks.append(len(dims))
    return ranks


def test_fused_hlo_has_no_gathered_qkv(one_chip):
    """The acceptance guarantee of the fused path, on the program the
    chip runs: the routing module with impl="pallas_fused" holds the
    kernel and no gathered (..., w, dh) q/k/v copy — the only dh-trailing
    gathers left are rank 2 (XLA's sorted scatter-add of the per-cluster
    outputs). The XLA reference is the positive control: it materializes
    rank >= 3 (B,H,k,w,dh) q/v copies."""
    from repro.configs.base import RoutingConfig
    from repro.core.kmeans import KMeansState
    from repro.core.routing import routed_attention
    x = _shape(one_chip, (1, HEADS, 8192, DH), "float32")
    mu = _shape(one_chip, (HEADS, KC, DH), "float32")
    cfg = RoutingConfig(num_clusters=KC)

    def run(impl):
        return lambda q, v, mu: routed_attention(
            q, None, v, KMeansState(mu=mu), cfg, update_state=False,
            impl=impl, interpret=False).out

    fused = _dh_gather_ranks(_compile(run("pallas_fused"), x, x, mu))
    gathered = _dh_gather_ranks(
        jax.jit(run("xla")).lower(x, x, mu).compile().as_text())
    assert all(r <= 2 for r in fused), fused
    assert any(r >= 3 for r in gathered), gathered
