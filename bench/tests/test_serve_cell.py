"""The serving mode's comparison with the reference, driven on the CPU at
a small size with the chip check skipped. In float32 the engine's greedy
tokens are the reference's best at every position (gap 0): the
reference's serving semantics (balanced routing over the prompt, arg-max
cluster pages in decode) are the engine's. In bfloat16 the engine's
reading is small, and the control (the reference in float8) and a token
altered where the engine produces it read several times more. No serving
cell is in BENCHMARK.json yet, so no limit is committed; the cell that
adds one sets it from chip readings (PERF.md, Open questions)."""
import json
from pathlib import Path

import jax
import pytest

from bench import faults
from bench.modes import serve
from bench.run import RunContext

BENCH = Path(__file__).resolve().parents[1]


def run_small(seed, dtype="bfloat16", control=False, fault=None):
    config = json.loads((BENCH / "configs" / "rt-enwik8-serve.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / "serve-mixed.json").read_text())
    config["model"].update(num_layers=2, d_model=128, num_heads=2,
                           routing_heads=1, head_dim=64, d_ff=256,
                           num_clusters=4, local_window=32, max_seq_len=512,
                           dtype=dtype)
    config["serve"].update(max_slots=4, max_len=288, decode_impl="xla")
    if control:
        config["control_rounding"] = config["control"]["rounding"]
    traffic.update(rate_per_s=3.0, check_requests=3,
                   prompt=dict(median=96, sigma=0.8, round=64, min=64,
                               max=256),
                   output=dict(median=8, sigma=0.8, min=2, max=32))
    ctx = RunContext(config=config, traffic=traffic, seed=seed, seconds=3,
                     trace=False, chips=1, devices=jax.devices())
    return serve.run(ctx, fault=fault)


def test_float32_engine_serves_the_references_best_tokens():
    out = run_small(3, dtype="float32")
    assert out["checks"]["served_logit_gap"] == 0.0
    assert out["failed"] == 0


@pytest.fixture(scope="module")
def program():
    return run_small(2 ** 33 + 1)


def test_engine_as_configured(program):
    m = program["metrics"]
    assert program["window_compiles"] == 0 and program["failed"] == 0
    assert m["serve_output_tokens_per_s"] > 0
    assert 0 <= m["serve_ttft_p95_s"] < float("inf")
    assert 0 <= m["serve_itl_p95_s"] < float("inf")


@pytest.mark.parametrize("case", ["control", "altered_token"])
def test_control_and_fault_read_far_above_the_engine(program, case):
    out = (run_small(2 ** 33 + 1, control=True) if case == "control"
           else run_small(2 ** 33 + 1,
                          fault=faults.FAULTS["serve"]["altered_token"]))
    assert out["checks"]["served_logit_gap"] >= \
        3 * program["checks"]["served_logit_gap"]
