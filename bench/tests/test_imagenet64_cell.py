"""The ImageNet-64 training cell (``train-imagenet64-12k``): its
correctness comparison driven on the CPU at a small size with the chip
check skipped, as ``test_train_cell.py`` drives the enwik8 cell — the
program as configured comes out correct against the blocked reference;
the control (the program's own bfloat16 path) and each training fault
come out not correct, against the cell's committed limits. The small
size keeps the cell's shape: dh 64, a local window (1024) past the
local kernel's query sub-tile (256 rows), and routing clusters of 256.
And, at the cell's own shapes, the attention it resolves to on a TPU:
both halves on the Pallas kernels."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import faults
from bench.modes import train
from bench.run import RunContext, judge, load_cell

WORKLOAD = "train-imagenet64-12k"
ROOT = Path(__file__).resolve().parents[2]


def small(dtype="float32"):
    _, cell, config, traffic, limits = load_cell(WORKLOAD, ROOT)
    assert config["reference"] == "routing_lm_blocked"
    config["model"].update(num_layers=2, d_model=128, num_heads=2,
                           routing_heads=1, d_ff=256, num_clusters=8,
                           local_window=1024, max_seq_len=2048, dtype=dtype)
    traffic = dict(traffic, seq_len=2048, rows_per_chip=2, pool_batches=4)
    return config, traffic, limits


def run_small(seed, dtype="float32", fault=None):
    config, traffic, limits = small(dtype)
    ctx = RunContext(config=config, traffic=traffic, seed=seed, seconds=0.5,
                     trace=False, chips=1, devices=jax.devices())
    out = train.run(ctx, fault=fault)
    correct, checks = judge(out["checks"], limits)
    return correct, checks, out


def test_small_cell_keeps_the_kernels_shapes():
    from repro.kernels.local_attention import sub_tile
    c = small()[0]["model"]
    assert c["head_dim"] == 64
    assert sub_tile(c["local_window"], c["head_dim"]) < c["local_window"]


def test_program_as_configured_is_correct():
    correct, checks, out = run_small(2 ** 33 + 5)
    assert correct, checks
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert out["metrics"]["train_tokens_per_s"] > 0


def test_control_bfloat16_is_not_correct():
    correct, checks, _ = run_small(7, dtype="bfloat16")
    assert not correct, checks


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["train"]))
def test_fault_is_not_correct(fault):
    correct, checks, _ = run_small(9, fault=faults.FAULTS["train"][fault])
    assert not correct, checks


def test_backend_at_the_cells_shapes_is_pallas_fused(monkeypatch):
    """On a TPU the cell's attention resolves to local+routing/
    pallas_fused and runs both halves on the Pallas kernels: the local
    window kernel at w = 2048 and the fused routing kernel in clusters of
    1536, with no XLA attention (traced, not run)."""
    from repro import attn as A
    from repro.attn import backends
    from repro.kernels import ops as kops
    _, _, config, traffic, _ = load_cell(WORKLOAD, ROOT)
    c, n = config["model"], traffic["seq_len"]
    (spec,) = A.specs_for_model(train.program_config(c))
    assert spec.variant == "local+routing" and spec.window == 2048
    backend = A.resolve(spec, platform="tpu", needs_grad=True, seq_len=n)
    assert backend.name == "local+routing/pallas_fused"

    calls = []

    def spy(name, fn):
        def f(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        return f

    for name in ("local_attention", "routed_attention_fused"):
        monkeypatch.setattr(kops, name, spy(name, getattr(kops, name)))
    for name in ("local_attention", "full_attention"):
        monkeypatch.setattr(backends, name, spy("xla/" + name,
                                                getattr(backends, name)))
    h, dh = c["num_heads"], c["head_dim"]
    x = jax.ShapeDtypeStruct((1, h, n, dh), jnp.float32)
    mu = jax.ShapeDtypeStruct((c["routing_heads"], c["num_clusters"], dh),
                              jnp.float32)

    def grad(q, k, v, mu):
        return jax.grad(lambda q, k, v: A.attend(
            spec, q, k, v, state=mu, needs_grad=True, update_state=False,
            platform="tpu").out.sum(), argnums=(0, 1, 2))(q, k, v)

    jax.eval_shape(grad, x, x, x, mu)
    assert sorted(set(calls)) == ["local_attention",
                                  "routed_attention_fused"], calls
