"""``local_attention_roofline.train``: the local window kernels' FLOPs
and bytes worked by hand at rt-enwik8's and rt-imagenet64's shapes, and
the reader on hand-made device operations."""
import json
from pathlib import Path

import pytest

from bench import peaks, spans
from bench import trace_reduce as tr
from bench.run import metric_reader
from bench.trace_reduce import Op

NAME = "local_attention_roofline.train"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PEAK = peaks.lookup("TPU v5 lite")


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def kernel_counts():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "local_roofline", CONFIGS.parent / "metrics" / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.kernel_counts


def test_counts_one_call_by_hand():
    # rt-enwik8 cut to one layer and one local head (8 heads, 7 routing)
    c = dict(model("rt-enwik8-train"), num_layers=1, routing_heads=7)
    flops, nbytes = kernel_counts()(c, 8192, 1, 4)
    # 376.5 keys a query (bench/tests/test_counts.py), 4*dh a pair, x3
    assert flops == 3 * 4 * 128 * 376.5 * 8192
    plane, rows = 8192 * 128 * 4, 8192 * 4
    assert nbytes == (4 * plane + rows) + (7 * plane + 2 * rows)


def test_counts_scale_with_rows_layers_and_local_heads():
    c = model("rt-imagenet64-train")
    one = kernel_counts()(dict(c, num_layers=1, routing_heads=15),
                          12288, 1, 4)
    all_ = kernel_counts()(c, 12288, 2, 4)
    assert all_ == (one[0] * 2 * 24 * 8, one[1] * 2 * 24 * 8)
    # w = 2048: six blocks, the first without a previous one
    own, prev = 2048 * 2049 / 2 * 6, 2048 ** 2 * 5
    assert one[0] == 3 * 4 * 64 * (own + prev)


OPS = [Op("local_attention.3", 0, 400), Op("fusion.4", 400, 500),
       Op("local_attention.5", 500, 900)]
SCOPES = {
    "local_attention.3": "jit(s)/train/grad/jvp(model/stack)/jit(local_"
                         "attention)/kernels/local_attention/pallas_call",
    "fusion.4": "jit(s)/train/grad/transpose(jvp(model/stack))/jit(local_"
                "attention)/kernels/local_attention/mul",
    "local_attention.5": "jit(s)/train/grad/transpose(jvp(model/stack))/"
                         "jit(local_attention)/kernels/local_attention/"
                         "pallas_call",
}
HOST = [Op("bench/window", 0, 1000), Op("train", 0, 1000)]


def _ctx(monkeypatch, ops=OPS, mode="train"):
    monkeypatch.setattr(spans, "load", lambda red: ({0: ops}, HOST, SCOPES))
    c = model("rt-enwik8-train")
    return {"mode": mode, "trace": tr.reduce_ops({0: ops}, HOST),
            "steps": 1, "notes": [], "config": c, "peak": PEAK,
            "traffic": {"seq_len": 8192}, "rows_per_chip": 4,
            "elem_bytes": 4}


def test_reader_on_hand_made_ops(monkeypatch):
    ctx = _ctx(monkeypatch)
    flops, nbytes = kernel_counts()(ctx["config"], 8192, 4, 4)
    least = max(flops / PEAK["bf16_flops"], nbytes / PEAK["hbm_bytes_per_s"])
    assert metric_reader(NAME)(ctx) == pytest.approx(100 * least / 800e-9)
    note = ctx["notes"][-1]
    # the kernels' 800 ns beside the span's 900 (the wrapper's fusion)
    assert note.startswith("[local_attention] 0.000001 s on the device")
    assert "span kernels/local_attention 0.000001 s" in note


@pytest.mark.parametrize("case", ["serve", "no_kernel"])
def test_reader_returns_none_without_the_kernel(monkeypatch, case):
    if case == "serve":
        ctx = _ctx(monkeypatch, mode="serve")
    else:
        ctx = _ctx(monkeypatch, ops=[Op("fusion.4", 400, 500)])
    assert metric_reader(NAME)(ctx) is None
