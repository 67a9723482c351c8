"""bench/spans.py: the scope map that a CPU profile of a small routed
train step holds puts the forward, remat and backward copies of the
routing stages under their spans; the profile of the window is found by
its window; the span readers on hand-made operations."""
import importlib.util
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import spans
from bench import trace_reduce as tr
from bench.trace_reduce import Op

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def step_profile(tmp_path_factory):
    """(temporary directory, profile) of one 2-layer local+routing train
    step with full remat, traced as ``bench/run.py`` traces its window."""
    from repro.configs.base import (ModelConfig, RoutingConfig, RunConfig,
                                    TrainConfig)
    from repro.train.train_step import init_train_state, make_train_step
    cfg = ModelConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, head_dim=32, d_ff=128, vocab_size=64,
        max_seq_len=64, attention="local+routing",
        routing=RoutingConfig(num_clusters=4, local_window=16,
                              routing_heads=1, share_qk=True),
        attn_window=16, position="rope", norm="layernorm", act="relu",
        dropout=0.1, dtype="float32")
    run = RunConfig(model=cfg, train=TrainConfig(
        global_batch=2, seq_len=64, steps=1, remat="full"))
    state = init_train_state(run, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 65), jnp.int32)}
    step = jax.jit(make_train_step(run))
    jax.block_until_ready(step(state, batch))
    tmp = tmp_path_factory.mktemp("tmp")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-", dir=tmp)
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        jax.block_until_ready(step(state, batch))
    jax.profiler.stop_trace()
    return tmp, sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]


@pytest.fixture(scope="module")
def step_scopes(step_profile):
    return spans.trace_scopes(step_profile[1])


def _copies(scopes, span):
    """Which copies of ``span`` the map holds: forward, remat recompute,
    backward."""
    got = set()
    for op in scopes.values():
        if span not in spans.spans_of(op):
            continue
        if "rematted_computation" in op:
            got.add("remat")
        elif "transpose(" in op:
            got.add("backward")
        else:
            got.add("forward")
    return got


@pytest.mark.parametrize("span", ["routing/assign", "routing/scatter"])
def test_scope_map_holds_forward_remat_and_backward(step_scopes, span):
    assert _copies(step_scopes, span) == {"forward", "remat", "backward"}


def test_scope_map_names_the_layers(step_scopes):
    leaves = {spans.leaf(op) for op in step_scopes.values()}
    assert {"model/attention_proj", "model/ffn", "model/loss",
            "model/embed", "model/stack", "routing/kmeans_update",
            "train/optimizer"} <= leaves


HLO = """\
HloModule m

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %scatter.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/train/grad/routing/scatter/scatter-add"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%t), index=1, metadata={op_name="jit(f)/train/grad/model/stack/while/body/gte"}
  %fusion.7 = f32[4]{0} fusion(%gte.1), kind=kCustom, calls=%fused_computation.1
  %copy.3 = f32[4]{0} copy(%fusion.7)
  %copy.4 = f32[4]{0} copy(%gte.1)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.1, %copy.3)
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %routed_attention_fused.3 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/train/grad/jit(routed_attention_fused)/kernels/routed_attention_fused/pallas_call"}
  %while.1 = (s32[], f32[4]{0}) while(%routed_attention_fused.3), condition=%cond.1, body=%body.2, metadata={op_name="jit(f)/train/grad/transpose(jvp(model/stack))/while"}
  ROOT %add.5 = f32[4]{0} add(%a, %a), metadata={op_name="jit(f)/train/grad/transpose(jvp(model/loss))/add"}
}
"""


def test_trace_modules_print_the_metadata(step_profile):
    modules = spans.trace_modules(step_profile[1])
    assert modules and "routing/assign" in modules[0]
    assert "op_name=" in modules[0]


@pytest.mark.parametrize("case", ["this_window", "other_window",
                                  "no_profile"])
def test_load_takes_the_profile_of_the_window(monkeypatch, step_profile,
                                              tmp_path, case):
    tmp, path = step_profile
    monkeypatch.setattr(tempfile, "tempdir",
                        str(tmp_path if case == "no_profile" else tmp))
    devices, host = tr.read_planes(path)
    win = [h for h in host if h.name == tr.WINDOW_SPAN]
    if case == "other_window":
        win = [Op(tr.WINDOW_SPAN, win[0].start, win[0].end + 1)]
    red = tr.reduce_ops({0: [Op("x", win[0].start, win[0].end)]}, win)
    got = spans.load(red)
    if case != "this_window":
        assert got is None
        return
    assert spans.window_s(got[1]) == red.window_s
    assert any(spans.leaf(op) == "routing/scatter"
               for op in got[2].values())


def test_scope_map_fills_what_xla_left_without_metadata():
    m = spans.scope_map(HLO)
    # a fusion without metadata: its computation's root
    assert spans.leaf(m["fusion.7"]) == "routing/scatter"
    # a copy without metadata: its first operand that has one
    assert spans.leaf(m["copy.3"]) == "routing/scatter"
    # the first operand names the loop's own work
    assert spans.leaf(m["copy.4"]) == "model/stack"
    assert spans.leaf(m["routed_attention_fused.3"]) == \
        "kernels/routed_attention_fused"
    assert spans.leaf(m["add.5"]) == "model/loss"
    assert spans.leaf(m["a"]) == spans.UNCLAIMED


def test_spans_of_and_leaf():
    op = ("jit(f)/train/grad/transpose(jvp(model/stack))/while/body/"
          "checkpoint/rematted_computation/routing/assign/jit(sort)/sort")
    assert spans.spans_of(op) == ["train/grad", "model/stack",
                                  "routing/assign"]
    assert spans.leaf(op) == "routing/assign"
    assert spans.leaf("jit(f)/train/grad/add") == spans.UNCLAIMED
    assert spans.leaf("") == spans.UNCLAIMED


# one chip, a window of 1000 ns holding two steps: device ops, and the
# trainer's host spans around them
OPS = [Op("sort.1", 100, 200), Op("fusion.2", 200, 250),
       Op("scatter.3", 250, 400), Op("fusion.4", 500, 600),
       Op("routed_attention_fused.5", 600, 900)]
SCOPES = {
    "sort.1": "jit(s)/train/grad/jvp(model/stack)/routing/assign/sort",
    "fusion.2": "jit(s)/train/grad/transpose(jvp(model/stack))/"
                "checkpoint/rematted_computation/routing/assign/div",
    "scatter.3": "jit(s)/train/grad/jvp(model/stack)/routing/scatter/"
                 "scatter-add",
    "fusion.4": "jit(s)/train/grad/add",
    "routed_attention_fused.5": "jit(s)/train/grad/jit(routed_attention_"
                                "fused)/kernels/routed_attention_fused/"
                                "pallas_call",
}
HOST = [Op("bench/window", 0, 1000),
        Op("train", 0, 480), Op("train/data", 0, 40),
        Op("train/dispatch", 40, 90), Op("train/fetch", 90, 480),
        # idle 400..480 inside train/fetch; 480..500 under no trainer span
        Op("train", 500, 1000), Op("train/fetch", 900, 960)]


def _ctx(monkeypatch, mode="train", scopes=SCOPES, host=HOST,
         profile=True):
    monkeypatch.setattr(spans, "load", lambda red: (
        ({0: OPS}, host, scopes) if profile else None))
    return {"mode": mode, "trace": tr.reduce_ops({0: OPS}, host),
            "steps": 2, "notes": []}


def test_readers_on_hand_made_ops(monkeypatch):
    ctx = _ctx(monkeypatch)
    busy = ctx["trace"].busy_s
    assert busy == pytest.approx(700e-9)
    assert reader("kmeans_assign_share.train")(ctx) == pytest.approx(
        100 * 150e-9 / busy)
    assert reader("routing_scatter_share.train")(ctx) == pytest.approx(
        100 * 150e-9 / busy)
    # idle 0..100 (data, dispatch: 90 ns, then 10 ns of fetch), 400..480
    # in fetch, 480..500 in no span, 900..960 in fetch, 960..1000 none
    assert reader("trainer_exposed_ms.train")(ctx) == pytest.approx(
        1e3 * (100 + 80 + 60) * 1e-9 / 2)
    idle = spans.attribution(ctx)["idle"]
    assert idle["train/fetch"] == pytest.approx(150e-9)
    assert idle["train/data"] == pytest.approx(40e-9)
    assert idle[""] == pytest.approx(60e-9)
    notes = "\n".join(ctx["notes"])
    assert "Reduced.kernel_s('routed_attention_fused')" in notes
    assert "(unclaimed)" in notes and "train/fetch" in notes


def test_attribution_is_computed_once(monkeypatch):
    ctx = _ctx(monkeypatch)
    first = spans.attribution(ctx)
    n = len(ctx["notes"])
    assert spans.attribution(ctx) is first and len(ctx["notes"]) == n


def test_leaf_claims_and_kernel_span(monkeypatch):
    ctx = _ctx(monkeypatch)
    by_leaf = spans.attribution(ctx)["by_leaf"]
    assert by_leaf[spans.UNCLAIMED] == pytest.approx(100e-9)
    assert by_leaf["kernels/routed_attention_fused"] == pytest.approx(
        ctx["trace"].kernel_s("routed_attention_fused"))


@pytest.mark.parametrize("name", ["kmeans_assign_share.train",
                                  "routing_scatter_share.train",
                                  "trainer_exposed_ms.train"])
@pytest.mark.parametrize("case", ["serve", "no_scope_map", "no_spans",
                                  "no_profile"])
def test_readers_return_none_without_spans(monkeypatch, name, case):
    if case == "serve":
        ctx = _ctx(monkeypatch, mode="serve")
    elif case == "no_scope_map":
        # a profile that holds no compiled module
        ctx = _ctx(monkeypatch, scopes={})
    elif case == "no_profile":
        ctx = _ctx(monkeypatch, profile=False)
    else:
        # a program that writes no span: nothing to read, and not 0
        ctx = _ctx(monkeypatch,
                   scopes={k: "jit(s)/add" for k in SCOPES},
                   host=[h for h in HOST if not h.name.startswith("train")])
    assert reader(name)(ctx) is None
