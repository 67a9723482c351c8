"""Observability subsystem: metrics core, JSONL schema, routing-health
invariants, the stats-off no-op guarantee, and the telemetry smokes.

The load-bearing test is the HLO byte-identity pair: RoutingConfig.stats
is a *static* python conditional, so stats=False must compile the exact
program the field's default compiles — telemetry that is off can never
perturb numerics, layouts, or fusion decisions.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ModelConfig, RoutingConfig, RunConfig,
                                TrainConfig)
from repro.core.kmeans import KMeansState, init_kmeans
from repro.core.routing import routed_attention
from repro.obs import (Counter, Gauge, Histogram, JsonlSink, Registry,
                       SCHEMA_VERSION, StepSeries)
from repro.obs.routing_stats import RoutingStats, pages_health, summarize
from repro.obs.schema import SchemaError, validate_jsonl, validate_record
from repro.obs.trace import profile, span, step_span


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------
def test_registry_instruments():
    reg = Registry()
    reg.counter("steps").inc()
    reg.counter("steps").inc(2)
    reg.gauge("lr").set(3e-4)
    h = reg.histogram("lat")
    for v in (4.0, 1.0, 2.0, 3.0):
        h.record(v)
    s = reg.summary()
    assert s["steps"] == 3.0
    assert s["lr"] == pytest.approx(3e-4)
    assert s["lat.count"] == 4 and s["lat.min"] == 1.0 and s["lat.max"] == 4.0
    # linear interpolation on the sorted sample, numpy semantics
    assert h.percentile(50) == pytest.approx(
        float(np.percentile([1, 2, 3, 4], 50)))
    assert h.percentile(90) == pytest.approx(
        float(np.percentile([1, 2, 3, 4], 90)))


def test_registry_type_mismatch_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_empty_and_singleton():
    h = Histogram("h")
    assert h.percentile(50) is None
    assert h.summary()["count"] == 0
    h.record(7.0)
    assert h.percentile(99) == 7.0


# ---------------------------------------------------------------------------
# JSONL sink + schema
# ---------------------------------------------------------------------------
def test_jsonl_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    with JsonlSink(path, source="test") as sink:
        sink.emit("train_step", metrics={"loss": jnp.float32(1.5),
                                         "vec": jnp.arange(3.0)}, step=0)
        sink.emit("engine_tick", metrics={"active_slots": 2.0}, step=1,
                  uid=7)
    assert validate_jsonl(path) == 2
    recs = [json.loads(ln) for ln in open(path)]
    assert recs[0]["v"] == SCHEMA_VERSION
    assert recs[0]["metrics"]["loss"] == 1.5          # device -> host float
    assert recs[0]["metrics"]["vec"] == [0.0, 1.0, 2.0]
    assert recs[1]["uid"] == 7


def test_schema_rejects_tampered_lines(tmp_path):
    good = {"v": SCHEMA_VERSION, "kind": "x", "t": 0.0}
    validate_record(good)
    for bad in ({**good, "v": 99},            # wrong schema version
                {**good, "kind": ""},         # empty kind
                {**good, "t": float("nan")},  # non-finite timestamp
                {**good, "step": -1},
                {**good, "metrics": {"a": float("inf")}}):
        with pytest.raises(SchemaError):
            validate_record(bad)
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write("not json\n")
    with pytest.raises(SchemaError):
        validate_jsonl(path)


def test_schema_whole_file_json_mode(tmp_path):
    """CI's docs-check runs the CLI over committed bench records: whole
    .json files are held to strict finite JSON (bare NaN rejected even
    though json.loads accepts it)."""
    from repro.obs.schema import main as schema_main
    from repro.obs.schema import validate_json_file
    ok = tmp_path / "BENCH_x.json"
    ok.write_text(json.dumps({"speedup": 2.5, "backends":
                              {"routing": "pallas_paged"}, "note": None}))
    validate_json_file(str(ok))
    assert schema_main([str(ok)]) == 0
    for payload in ('{"x": NaN}',            # json.loads-accepted, invalid
                    '{"x": Infinity}',
                    '{"x": 1,}'):            # not JSON at all
        bad = tmp_path / "bad.json"
        bad.write_text(payload)
        with pytest.raises(SchemaError):
            validate_json_file(str(bad))
        assert schema_main([str(bad)]) == 1


def test_committed_records_and_docs_pass_checks():
    """The repo's own committed artifacts/docs satisfy the CI docs-check
    step (anchor linter + whole-file record validation)."""
    import importlib.util
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    from repro.obs.schema import validate_json_file
    records = sorted((root / "benchmarks").glob("*smoke*.json"))
    assert records
    for rec in records:
        validate_json_file(str(rec))
    spec = importlib.util.spec_from_file_location(
        "check_docs", root / "tools" / "check_docs.py")
    check_docs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_docs)
    assert check_docs.check(root) == []


def test_step_series_history(tmp_path):
    path = str(tmp_path / "s.jsonl")
    series = StepSeries(sink=JsonlSink(path), kind="train_step")
    series.record(0, {"loss": jnp.float32(2.0)})
    series.record(1, {"loss": jnp.float32(1.0)})
    assert [r["loss"] for r in series.history] == [2.0, 1.0]
    assert validate_jsonl(path) == 2


# ---------------------------------------------------------------------------
# routing-health invariants (full routed_attention, stats on)
# ---------------------------------------------------------------------------
def _routing_inputs(B=2, H=2, N=128, dh=32, kc=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, N, dh))
    v = jax.random.normal(ks[1], (B, H, N, dh))
    st = init_kmeans(ks[2], H, kc, dh)
    return q, v, st


def test_routing_stats_invariants():
    B, H, N, kc = 2, 2, 128, 4
    q, v, st = _routing_inputs(B=B, H=H, N=N, kc=kc)
    cfg = RoutingConfig(num_clusters=kc, stats=True)
    out = routed_attention(q, None, v, st, cfg, update_state=True)
    s = jax.device_get(out.stats)
    assert isinstance(out.stats, RoutingStats)
    # occupancy: batch-mean token counts sum to N per head (no padding)
    assert s.occupancy.shape == (H, kc)
    np.testing.assert_allclose(s.occupancy.sum(-1), N, rtol=1e-5)
    # dead = centroids with zero occupancy
    np.testing.assert_allclose(s.dead, (s.occupancy <= 0).sum(-1), atol=1e-5)
    assert np.all(s.entropy >= -1e-5)
    assert np.all(s.entropy <= math.log(kc) + 1e-5)
    assert np.all((s.mismatch >= -1e-5) & (s.mismatch <= 1 + 1e-5))
    assert np.all((s.recall >= -1e-5) & (s.recall <= 1 + 1e-5))
    assert np.all(s.drift > 0)          # EMA moved the centroids
    # update_state=False freezes the centroids -> zero drift
    out2 = routed_attention(q, None, v, st, cfg, update_state=False)
    np.testing.assert_allclose(jax.device_get(out2.stats.drift), 0.0,
                               atol=1e-7)


def test_routing_stats_padding_excluded():
    B, H, N, kc = 2, 2, 128, 4
    q, v, st = _routing_inputs(B=B, H=H, N=N, kc=kc)
    pad = jnp.arange(N)[None, :] < (N // 2)
    pad = jnp.broadcast_to(pad, (B, N))
    cfg = RoutingConfig(num_clusters=kc, stats=True)
    out = routed_attention(q, None, v, st, cfg, pad_mask=pad,
                           update_state=False)
    s = jax.device_get(out.stats)
    np.testing.assert_allclose(s.occupancy.sum(-1), N // 2, rtol=1e-5)


def test_routing_stats_detect_collapse():
    """All tokens routed to one centroid -> entropy ~0, dead = k-1."""
    B, H, N, dh, kc = 1, 1, 64, 32, 4
    vec = jnp.linspace(-1.0, 1.0, dh)            # fixed routing direction
    q = jnp.broadcast_to(vec, (B, H, N, dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, H, N, dh))
    from repro.core.kmeans import normalize_routing
    r = normalize_routing(vec[None])[0]           # what routing sees
    mu = jnp.stack([r] + [-r] * (kc - 1))[None]   # (H,kc,dh): mu[0] wins
    st = KMeansState(mu=mu.astype(jnp.float32))
    cfg = RoutingConfig(num_clusters=kc, stats=True)
    out = routed_attention(q, None, v, st, cfg, update_state=False)
    s = jax.device_get(out.stats)
    assert float(s.entropy[0]) == pytest.approx(0.0, abs=1e-5)
    assert float(s.dead[0]) == kc - 1
    assert float(s.occupancy[0, 0]) == N


def test_summarize_folds_tree():
    q, v, st = _routing_inputs()
    cfg = RoutingConfig(num_clusters=4, stats=True)
    stats = routed_attention(q, None, v, st, cfg, update_state=False).stats
    summ = summarize([{"0": stats}, {}])
    assert set(summ) == {f"routing/{f}" for f in
                         ("entropy", "dead", "drift", "mismatch", "recall",
                          "live_tiles")}
    assert float(summ["routing/entropy"]) == pytest.approx(
        float(jnp.mean(stats.entropy)), rel=1e-6)
    assert summarize([{}, {}]) == {}


@pytest.mark.parametrize("T", [2, 12])
def test_live_tiles_reads_the_causal_band(T):
    """Unique sorted members at increasing positions keep to the causal
    band: T(T+1)/2 of T² sub-tile pairs (78/144 at T = 12, rt-imagenet64's
    w 1536; 3/4 at T = 2, rt-enwik8's w 256); non-causal, unpadded, all."""
    from repro.kernels.routing_attention import computed_tile_share
    B, H, kc, b = 2, 3, 4, 16
    w = T * b
    N = kc * w
    perms = [jax.random.permutation(k, N)
             for k in jax.random.split(jax.random.PRNGKey(T), B * H)]
    idx = jnp.sort(jnp.stack(perms).reshape(B, H, kc, w), axis=-1)
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    band = computed_tile_share(pos, idx, None, True, bq=b, bk=b)
    np.testing.assert_allclose(band, T * (T + 1) / (2 * T * T), rtol=1e-6)
    full = computed_tile_share(pos, idx, idx, False, bq=b, bk=b)
    np.testing.assert_array_equal(full, 1.0)
    # positions that restart mid-cluster reach past the band: every pair
    restart = computed_tile_share(pos % (w // 2), idx, None, True, bq=b,
                                  bk=b)
    assert np.all(np.asarray(restart) > T * (T + 1) / (2 * T * T))


def test_live_tiles_leaf_only_with_stats_on():
    """Through routed_attention at the kernel's own sub-tiles (128): w 256
    reads 3/4 causal and 1 non-causal; with stats off there is no stats
    leaf and the program is the default's."""
    B, H, N, kc = 1, 2, 512, 2
    q, v, st = _routing_inputs(B=B, H=H, N=N, kc=kc)
    on = routed_attention(q, None, v, st,
                          RoutingConfig(num_clusters=kc, stats=True),
                          update_state=False).stats
    np.testing.assert_allclose(on.live_tiles, 0.75, rtol=1e-6)
    assert "routing/live_tiles" in summarize([{"0": on}])
    k = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    nc = routed_attention(q, k, v, st,
                          RoutingConfig(num_clusters=kc, stats=True,
                                        causal=False, share_qk=False),
                          update_state=False).stats
    np.testing.assert_array_equal(nc.live_tiles, 1.0)
    off = RoutingConfig(num_clusters=kc, stats=False)
    assert routed_attention(q, None, v, st, off,
                            update_state=False).stats is None

    def lower(cfg):
        return jax.jit(lambda q, v: routed_attention(
            q, None, v, st, cfg, update_state=False)).lower(q, v).as_text()
    assert lower(off) == lower(RoutingConfig(num_clusters=kc))


def test_pages_health_reads_rlen():
    rlen = np.zeros((1, 2, 1, 4), np.int32)     # (G,B,Hr,kc)
    rlen[0, 0, 0] = [10, 10, 10, 10]            # balanced slot
    rlen[0, 1, 0] = [40, 0, 0, 0]               # collapsed slot
    h = pages_health([{"rlen": rlen}])
    assert h["routing/entropy"] == pytest.approx(
        (math.log(4) + 0.0) / 2, abs=1e-6)
    assert h["routing/dead"] == pytest.approx(1.5)
    # active mask drops the collapsed slot
    h0 = pages_health([{"rlen": rlen}], active=np.array([True, False]))
    assert h0["routing/dead"] == 0.0
    assert pages_health([{"k": np.zeros((1, 2, 1, 4))}]) is None
    assert pages_health([{"rlen": rlen}],
                        active=np.array([False, False])) is None


# ---------------------------------------------------------------------------
# stats off must be a true no-op: byte-identical HLO
# ---------------------------------------------------------------------------
def test_stats_off_hlo_identical_routed_attention():
    q, v, st = _routing_inputs()
    # lower the FULL output pytree: with stats off the stats slot is a
    # python None, so the traced program must be the default program to
    # the byte; returning only .out would let trace-time DCE hide a
    # stats computation that actually changed the jaxpr
    def lower(cfg):
        return jax.jit(lambda q, v: routed_attention(
            q, None, v, st, cfg, update_state=True)).lower(q, v).as_text()
    default = lower(RoutingConfig(num_clusters=4))
    off = lower(RoutingConfig(num_clusters=4, stats=False))
    on = lower(RoutingConfig(num_clusters=4, stats=True))
    assert off == default
    assert on != off                    # positive control: the knob acts


def _tiny_run(stats: bool) -> RunConfig:
    cfg = ModelConfig(name="obs-test", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, attention="local+routing",
                      routing=RoutingConfig(num_clusters=4, local_window=16,
                                            stats=stats),
                      dtype="float32")
    return RunConfig(model=cfg, train=TrainConfig(global_batch=2, seq_len=64,
                                                  steps=5, lr=1e-3))


def test_stats_off_hlo_identical_train_step():
    from repro.train.train_step import init_train_state, make_train_step
    batch = {"tokens": np.zeros((2, 64), np.int32)}
    state = init_train_state(_tiny_run(False), jax.random.PRNGKey(0))
    def lower(run):
        return jax.jit(make_train_step(run)).lower(state, batch).as_text()
    off, on = lower(_tiny_run(False)), lower(_tiny_run(True))
    assert off == lower(_tiny_run(False))       # deterministic lowering
    assert on != off


def test_train_step_metrics_carry_routing_stats():
    from repro.train.train_step import init_train_state, make_train_step
    run = _tiny_run(True)
    state = init_train_state(run, jax.random.PRNGKey(0))
    batch = {"tokens": np.random.RandomState(0).randint(
        0, 256, size=(2, 64)).astype(np.int32)}
    _, metrics = jax.jit(make_train_step(run))(state, batch)
    m = jax.device_get(metrics)
    assert 0.0 <= float(m["routing/entropy"]) <= math.log(4) + 1e-5
    assert "rt/0/0/entropy" in m                # per-layer detail
    # stats-off keeps the metric dict exactly as before
    state0 = init_train_state(_tiny_run(False), jax.random.PRNGKey(0))
    _, m0 = jax.jit(make_train_step(_tiny_run(False)))(state0, batch)
    assert not any(k.startswith(("routing/", "rt/")) for k in m0)


# ---------------------------------------------------------------------------
# end-to-end smokes: trainer + engine telemetry as schema-valid JSONL
# ---------------------------------------------------------------------------
def test_trainer_obs_jsonl(tmp_path):
    from repro.data.synthetic import SyntheticLoader
    from repro.train.trainer import Trainer
    path = str(tmp_path / "train.jsonl")
    run = _tiny_run(True)
    tr = Trainer(run, SyntheticLoader("markov", 256, 2, 64), obs_jsonl=path)
    out = tr.fit(3)
    tr.close()
    assert out["steps"] == 3
    assert len(tr.metrics_history) == 3
    assert validate_jsonl(path) == 3
    rec = json.loads(open(path).readline())
    assert rec["kind"] == "train_step" and rec["source"] == "trainer"
    assert 0.0 <= rec["metrics"]["routing/entropy"] <= math.log(4) + 1e-5
    assert rec["metrics"]["step_time_s"] > 0
    assert tr.obs.histogram("train/step_time_s").count == 3


def test_engine_obs_jsonl(tmp_path):
    from repro.models.model import init_model
    from repro.serve.engine import InferenceEngine, Request
    cfg = _tiny_run(False).model
    params, kstate = init_model(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "engine.jsonl")
    eng = InferenceEngine(cfg, params, kstate, max_slots=2, max_len=32,
                          obs_jsonl=path, routing_stats=True)
    eng.run([Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=4),
             Request(uid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=3)])
    summ = eng.metrics.summary()
    eng.close()
    assert validate_jsonl(path) >= 3
    kinds = [json.loads(ln)["kind"] for ln in open(path)]
    assert kinds.count("engine_prefill") == 2
    assert "engine_tick" in kinds and kinds[-1] == "engine_summary"
    pre = next(json.loads(ln) for ln in open(path)
               if json.loads(ln)["kind"] == "engine_prefill")
    assert 0.0 <= pre["metrics"]["routing/entropy"] <= math.log(4) + 1e-5
    tick = next((json.loads(ln) for ln in open(path)
                 if json.loads(ln)["kind"] == "engine_tick"
                 and "routing/entropy" in json.loads(ln)["metrics"]), None)
    assert tick is not None             # pages health on active slots
    assert tick["metrics"]["routing/drift"] == 0.0  # frozen centroids
    # percentile satellites ride on the same histograms
    assert "ttft_p50_s" in summ and "decode_step_p99_s" in summ
    assert summ["ttft_p50_s"] <= summ["ttft_p99_s"]


def test_engine_stats_do_not_change_outputs():
    """routing_stats is pure telemetry: identical greedy outputs."""
    from repro.models.model import init_model
    from repro.serve.engine import InferenceEngine, Request
    cfg = _tiny_run(False).model
    params, kstate = init_model(cfg, jax.random.PRNGKey(0))
    reqs = lambda: [Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=4)]
    out_plain = InferenceEngine(cfg, params, kstate, max_slots=1,
                                max_len=16).run(reqs())
    out_stats = InferenceEngine(cfg, params, kstate, max_slots=1,
                                max_len=16, routing_stats=True).run(reqs())
    assert out_plain == out_stats


# ---------------------------------------------------------------------------
# trace spans + profiler capture
# ---------------------------------------------------------------------------
def test_span_names_hlo_and_nests():
    def f(x):
        with span("test/outer"):
            with span("test/inner"):
                return x * 2.0
    # named_scope lands in op metadata, which the compiled module prints
    hlo = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
    assert "test/outer" in hlo and "inner" in hlo
    assert float(f(jnp.asarray(2.0))) == 4.0    # eager path works too


# modules that open spans inside the compiled train step
SPAN_MODULES = ("repro.core.routing", "repro.models.transformer",
                "repro.models.model", "repro.attn.backends",
                "repro.train.train_step", "repro.kernels.routing_attention",
                "repro.kernels.ops")


def _strip_metadata(hlo: str) -> str:
    import re
    body = hlo[hlo.index("ENTRY"):] if "ENTRY" in hlo else hlo
    return re.sub(r",? metadata=\{[^}]*\}", "", body)


def test_spans_change_only_metadata(monkeypatch):
    """The step's spans are metadata: with every span a no-op the
    compiled train step holds the same instructions, op_name aside."""
    import contextlib
    import importlib
    from repro.train.train_step import init_train_state, make_train_step
    run = _tiny_run(False)
    state = init_train_state(run, jax.random.PRNGKey(0))
    batch = {"tokens": np.zeros((2, 65), np.int32)}

    def compiled():
        return jax.jit(make_train_step(run)).lower(state, batch) \
            .compile().as_text()

    with_spans = compiled()
    assert "routing/assign" in with_spans and "model/ffn" in with_spans
    for name in SPAN_MODULES:
        monkeypatch.setattr(importlib.import_module(name), "span",
                            lambda _: contextlib.nullcontext())
    without = compiled()
    assert "routing/assign" not in without
    assert _strip_metadata(with_spans) == _strip_metadata(without)


class _Loader:
    def __iter__(self):
        return self

    def __next__(self):
        return {"tokens": np.zeros((1, 4), np.int32)}

    def state(self):
        return {}


def _sleep(x):
    import time
    time.sleep(0.05)
    return x


class _LateResult:
    """A result that reaches the host 50 ms after it is asked for, as an
    asynchronously dispatched step's does."""

    def __init__(self, value):
        self.value = value

    def __array__(self, dtype=None, copy=None):
        return np.asarray(_sleep(self.value), dtype=dtype)


def _callback_step(ts, batch):
    s = jax.pure_callback(_sleep, jax.ShapeDtypeStruct((), jnp.int32),
                          ts.step)
    return ts._replace(step=s + 1), {"loss": s.astype(jnp.float32)}


def _late_step(ts, batch):
    # returns at once; the metrics arrive when fetched
    return ts._replace(step=ts.step + 1), {"loss": _LateResult(0.0)}


@pytest.mark.parametrize("step_fn", [jax.jit(_callback_step), _late_step],
                         ids=["pure_callback", "late_result"])
def test_trainer_step_time_includes_the_device_wait(step_fn):
    """step_time_s runs to the step's metrics on the host: a step whose
    result is ready only after a 50 ms wait reads >= 50 ms, also where
    the dispatch itself returns at once."""
    from repro.train.train_step import TrainState
    from repro.train.trainer import Trainer
    tr = Trainer(_tiny_run(False), _Loader(), step_fn=step_fn)
    tr.state = TrainState({}, None, None, jnp.zeros((), jnp.int32))
    assert tr.fit(3)["steps"] == 3
    times = [h["step_time_s"] for h in tr.metrics_history]
    assert len(times) == 3 and min(times) >= 0.05
    assert tr.obs.histogram("train/step_time_s").percentile(50) >= 0.05


def test_step_span_nests_under_an_active_profiler(tmp_path):
    from jax.profiler import ProfileData
    d = str(tmp_path / "prof")
    with profile(d):
        for i in range(2):
            with step_span(i + 1), span("train/data"):
                jax.block_until_ready(jnp.ones((4,)) * i)
    with step_span(3):                  # no profiler: still a no-op
        pass
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    names = [e.name for p in ProfileData.from_file(found[0]).planes
             for ln in p.lines for e in ln.events]
    assert names.count("train/data") == 2 and "train" in names


def test_profile_writes_capture(tmp_path):
    d = str(tmp_path / "prof")
    with profile(d):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "profiler capture wrote no files"
    with profile(None):                 # falsy dir -> no-op
        pass
    assert not os.path.exists(str(tmp_path / "none"))


def test_schema_cli(tmp_path, capsys):
    from repro.obs.schema import main as schema_main
    path = str(tmp_path / "ok.jsonl")
    with JsonlSink(path, source="cli") as sink:
        sink.emit("x", metrics={"a": 1.0})
    assert schema_main([path]) == 0
    assert "1 records ok" in capsys.readouterr().out
    bad = str(tmp_path / "bad.jsonl")
    open(bad, "w").write("{}\n")
    assert schema_main([bad]) == 1
