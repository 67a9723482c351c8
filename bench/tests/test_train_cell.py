"""The training cell's correctness comparison, driven on the CPU at a
small size with the chip check skipped: the program as configured comes
out correct; the control (the program's own bfloat16 path) and each
fault a single-chip training cell can have (a step that returns its
state unchanged; half of the batch left out) come out not correct,
against the cell's committed limits."""
from pathlib import Path

import jax
import pytest

from bench import faults
from bench.modes import train
from bench.run import RunContext, judge, load_cell

WORKLOAD = "train-enwik8-8k"
ROOT = Path(__file__).resolve().parents[2]


def small(dtype="float32"):
    _, cell, config, traffic, limits = load_cell(WORKLOAD, ROOT)
    config["model"].update(num_layers=2, d_model=128, num_heads=2,
                           routing_heads=1, head_dim=64, d_ff=256,
                           num_clusters=4, local_window=32, max_seq_len=128,
                           dtype=dtype)
    traffic = dict(traffic, seq_len=128, rows_per_chip=2, pool_batches=4)
    return config, traffic, limits


def run_small(seed, dtype="float32", fault=None):
    config, traffic, limits = small(dtype)
    ctx = RunContext(config=config, traffic=traffic, seed=seed, seconds=0.5,
                     trace=False, chips=1, devices=jax.devices())
    out = train.run(ctx, fault=fault)
    correct, checks = judge(out["checks"], limits)
    return correct, checks, out


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
