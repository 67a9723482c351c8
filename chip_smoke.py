"""Smoke test of the main path on TPU, in one process.

Runs the system through the entry points a user calls, at the published
width and depth of ``rt-enwik8`` (12 layers, d_model 1024, 8 heads of
dh 128, 32 clusters, window 256, sequence 8192), with random weights made
from ``--seed``:

  device   the first device must be a TPU; nothing falls back to the CPU
  kernels  local window, fused routing (resident and paged plans, forward
           and gradient) and paged decode kernels, compiled, against their
           XLA references on the same inputs
  train    steps through ``launch.train.make_sharded_step`` + ``Trainer``
           (the path ``python -m repro.launch.train`` takes); step 1's loss
           and gradient norm against ``make_train_step(impl="xla")``; the
           compiled step must contain the Pallas kernels
  serve    ``InferenceEngine`` on a few bfloat16 requests; every request
           finishes at its length and decode resolves to the paged-decode
           kernel; the engine's prefill logits, in float32, match the
           full forward through ``impl="xla"``

``--chips 4`` runs only the multi-chip phase instead: the GSPMD train step
on a (data=2, model=2) mesh and the int8 error-feedback step on (4, 1),
each against the same steps on one device of the same host.

Usage, from the repository root::

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips

Any failed check exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way are one-off observations, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
CFG_NAME = "rt-enwik8"
SEQ = 8192
TRAIN_STEPS = 4
SERVE_PROMPTS = (384, 1024, 2048, 3072)     # prompt lengths (tokens)
SERVE_NEW = (16, 32, 48, 64)                # new tokens per request
FOUR_CHIP_LAYERS = 2

# Tolerances. Kernel outputs and gradients are compared with XLA
# references run at precision="highest". The local kernel's matmuls run
# at Mosaic's default, which rounds float32 operands to bfloat16 (~4e-3
# relative); the routing kernels' run in float32 (~1e-5). 2e-2 leaves
# room without admitting a wrong row, tile or mask (those give O(1)
# errors). bfloat16 decode rounds every operand.
KERNEL_RTOL = 2e-2
DECODE_RTOL = 5e-2
# Pallas train step vs the XLA-attention train step: the same model; only
# attention numerics differ (they also shift balanced top-k near ties).
LOSS_RTOL = 5e-3
GRAD_NORM_RTOL = 5e-2
# float32 engine prefill vs the float32 XLA forward, both at "highest":
# only summation order differs (~1e-6); the bound leaves room for a
# membership flip at an exact near tie, and is far below what bfloat16
# rounding alone moves these logits (3e-2 on the CPU, 2.5e-1 on a v5e)
PREFILL_RTOL = 1e-2
# four chips: GSPMD runs XLA attention where one device runs the kernels;
# int8_ef carries the documented 2% gate of the compressed exchange
GSPMD_LOSS_RTOL = 5e-3
INT8_LOSS_RTOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def rel_err(a, b) -> float:
    """||a - b|| / ||b||, in float32 on the host."""
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: first device is {d.platform}")
    check(len(devs) >= chips, f"need {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_kernels(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.attn import head_split
    from repro.core.kmeans import (cluster_scores, init_kmeans,
                                   normalize_routing)
    from repro.core.local import local_attention
    from repro.core.routing import _block_attention, balanced_topk
    from repro.kernels.local_attention import local_attention_kernel
    from repro.kernels.routing_attention import routed_attention_fused

    Hl, Hr, _, _ = head_split(cfg)
    dh, kc, W = cfg.head_dim_, cfg.routing.num_clusters, cfg.attn_window
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32 = jnp.float32

    bad = []        # every comparison runs; the phase fails at the end

    def report(name, err, tol):
        say(f"[kernels] {name}: rel_err={err:.3e} (tol {tol:g})")
        if not err <= tol:
            bad.append(f"{name}: rel_err {err:.3e} > {tol:g}")

    def highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    # --- local window kernel, forward and gradient
    q, k, v = (jax.random.normal(ks[i], (1, Hl, SEQ, dh), f32)
               for i in range(3))
    ct = jax.random.normal(ks[3], (1, Hl, SEQ, dh), f32)
    kern = lambda q, k, v: local_attention_kernel(q, k, v, W, causal=True,
                                                  interpret=False)
    refn = lambda q, k, v: local_attention(q, k, v, W, True)
    report("local fwd", rel_err(jax.jit(kern)(q, k, v),
                                highest(refn)(q, k, v)), KERNEL_RTOL)
    grad = lambda f: lambda q, k, v: jax.grad(
        lambda *x: (f(*x) * ct).sum(), argnums=(0, 1, 2))(q, k, v)
    for n, a, b in zip(("dq", "dk", "dv"), jax.jit(grad(kern))(q, k, v),
                       highest(grad(refn))(q, k, v)):
        report(f"local grad {n}", rel_err(a, b), KERNEL_RTOL)

    # --- fused routing kernel, both memory plans, shared-QK causal (the
    # LM setting), memberships from balanced top-k as the model makes them
    mu = init_kmeans(ks[6], Hr, kc, dh).mu

    def routing_case(n, key):
        """Inputs at sequence ``n``, the gathered XLA reference and the
        fused kernel on them."""
        w = n // kc
        kx, kv = jax.random.split(key)
        r = normalize_routing(jax.random.normal(kx, (1, Hr, n, dh), f32))
        v = jax.random.normal(kv, (1, Hr, n, dh), f32)
        idx = balanced_topk(cluster_scores(r, mu), w)
        pos = jnp.arange(n, dtype=jnp.int32)[None]

        def ref(r, v):
            g = lambda a: jnp.take_along_axis(
                a, idx.reshape(1, Hr, -1, 1), axis=2).reshape(
                    1, Hr, kc, w, a.shape[-1])
            pg = g(jnp.broadcast_to(pos[:, None, :, None],
                                    (1, Hr, n, 1)))[..., 0]
            return _block_attention(g(r), g(r), g(v), pg, pg, True, None,
                                    False)[0]

        def fused(r, v, paged=None):
            return routed_attention_fused(r, None, v, idx, idx, pos,
                                          causal=True, interpret=False,
                                          paged=paged)
        return r, v, ref, fused

    r, v, ref, fused = routing_case(SEQ, ks[4])
    ctg = jax.random.normal(ks[7], (1, Hr, kc, SEQ // kc, dh), f32)
    ref_out = highest(ref)(r, v)
    ref_grad = highest(lambda r, v: jax.grad(
        lambda r, v: (ref(r, v) * ctg).sum(), argnums=(0, 1))(r, v))(r, v)
    outs = {}
    for paged in (False, True):
        plan = "paged" if paged else "resident"
        outs[plan] = jax.jit(lambda r, v: fused(r, v, paged))(r, v)
        report(f"fused {plan} fwd", rel_err(outs[plan], ref_out),
               KERNEL_RTOL)
        g = jax.jit(lambda r, v: jax.grad(
            lambda r, v: (fused(r, v, paged) * ctg).sum(),
            argnums=(0, 1))(r, v))(r, v)
        for n, a, b in zip(("dr", "dv"), g, ref_grad):
            report(f"fused {plan} grad {n}", rel_err(a, b), KERNEL_RTOL)
    same = bool(jnp.array_equal(outs["resident"], outs["paged"]))
    say(f"[kernels] fused resident == paged forward, bitwise: {same}")
    if not same:
        bad.append("fused memory plans disagree")

    # --- both kernels at a serving prefill's shapes: bfloat16 inputs and
    # a cluster window (SERVE_PROMPTS[1] / 32 = 32 rows) under one tile
    n, bf = SERVE_PROMPTS[1], jnp.bfloat16
    r, v, ref, fused = routing_case(n, ks[2])
    r, v = r.astype(bf), v.astype(bf)
    report(f"fused bfloat16 fwd, seq {n}",
           rel_err(jax.jit(fused)(r, v),
                   highest(ref)(r.astype(f32), v.astype(f32))), KERNEL_RTOL)
    q, k, v = (jax.random.normal(kk, (1, Hl, n, dh), bf)
               for kk in jax.random.split(ks[1], 3))
    report(f"local bfloat16 fwd, seq {n}",
           rel_err(jax.jit(kern)(q, k, v),
                   highest(refn)(*(a.astype(f32) for a in (q, k, v)))),
           KERNEL_RTOL)

    # --- paged decode kernel through the attention registry (bfloat16,
    # 8 sessions), against the XLA cluster-page decode on the same cache
    from repro import attn
    from repro.attn.spec import AttentionSpec
    from repro.configs.base import RoutingConfig
    B, cap = 8, 256
    spec = AttentionSpec(variant="routing", num_heads=Hr, num_kv_heads=Hr,
                         head_dim=dh,
                         routing=RoutingConfig(num_clusters=kc, window=cap))
    kd = jax.random.split(ks[0], 6)
    cache = {"rk": normalize_routing(jax.random.normal(
                 kd[0], (B, Hr, kc, cap, dh))).astype(bf),
             "rv": jax.random.normal(kd[1], (B, Hr, kc, cap, dh), bf),
             "rlen": jax.random.randint(kd[2], (B, Hr, kc), 0, 2 * cap)}
    qd = jax.random.normal(kd[3], (B, Hr, 1, dh), bf)
    vd = jax.random.normal(kd[4], (B, Hr, 1, dh), bf)
    dpos = jnp.full((B,), SEQ, jnp.int32)
    res = {}
    for impl in ("xla", "pallas_paged"):
        step = jax.jit(lambda c, q, v, impl=impl: attn.attend(
            spec, q, q, v, state=mu, cache=c, pos=dpos, impl=impl))
        res[impl] = step(cache, qd, vd)
    report("paged decode", rel_err(res["pallas_paged"].out,
                                   res["xla"].out), DECODE_RTOL)
    same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(res["xla"].cache),
        jax.tree.leaves(res["pallas_paged"].cache)))
    say(f"[kernels] paged decode cache == xla cache, bitwise: {same}")
    if not same:
        bad.append("paged decode cache write diverged")
    check(not bad, "; ".join(bad))


def _train_run(cfg, batch: int, **train_kw):
    from repro.configs.base import RunConfig, TrainConfig
    return RunConfig(model=cfg, train=TrainConfig(
        global_batch=batch, seq_len=SEQ, steps=TRAIN_STEPS, lr=1e-3,
        schedule="linear_warmup_rsqrt", warmup_steps=20, **train_kw))


def _loader(run, seed):
    from repro.data.synthetic import SyntheticLoader
    return SyntheticLoader("markov", min(run.model.vocab_size, 512),
                           run.train.global_batch, run.train.seq_len,
                           seed=seed)


def phase_train(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.distributed import make_process_mesh
    from repro.launch.train import make_sharded_step
    from repro.train.train_step import make_train_step
    from repro.train.trainer import Trainer

    run = _train_run(cfg, batch=1)
    mesh = make_process_mesh(1, 1)
    step, ts_spec = make_sharded_step(run, mesh)
    tr = Trainer(run, _loader(run, seed), mesh=mesh, shardings=ts_spec,
                 step_fn=step)
    with mesh:
        ts = tr.init_or_restore()
    n_params = sum(x.size for x in jax.tree.leaves(ts.params))
    say(f"[train] {cfg.name}: {n_params / 1e6:.1f}M params, batch 1 x "
        f"seq {SEQ}, float32")
    batch0 = {k: jnp.asarray(v) for k, v in next(_loader(run, seed)).items()}

    # step 1 on the XLA attention reference, same state and batch (not
    # donated: the trainer still owns ``ts``)
    _, m_ref = jax.jit(make_train_step(run, impl="xla"))(ts, batch0)
    m_ref = jax.device_get(m_ref)

    t0 = time.perf_counter()
    with mesh:
        hlo = step.jitted.lower(ts, jax.device_put(batch0)).compile()
    say(f"[train] compile_s={time.perf_counter() - t0:.1f} (Pallas step)")
    n_calls = hlo.as_text().count("tpu_custom_call")
    say(f"[train] compiled step has {n_calls} tpu_custom_call ops")
    check(n_calls > 0, "train step HLO has no tpu_custom_call")

    t0 = time.perf_counter()
    tr.fit(1)
    t1 = time.perf_counter()
    tr.fit(TRAIN_STEPS)
    t2 = time.perf_counter()
    say(f"[train] first_step_s={t1 - t0:.2f} "
        f"steady_step_s={(t2 - t1) / (TRAIN_STEPS - 1):.3f} "
        f"(host clock, metrics fetched each step)")
    hist = tr.metrics_history
    losses = [float(h["loss"]) for h in hist]
    say(f"[train] losses={[round(x, 5) for x in losses]}")
    check(len(losses) == TRAIN_STEPS, f"ran {len(losses)} steps")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    for key, tol in (("loss", LOSS_RTOL), ("grad_norm", GRAD_NORM_RTOL)):
        got, want = float(hist[0][key]), float(m_ref[key])
        err = abs(got - want) / max(abs(want), 1e-30)
        say(f"[train] step-1 {key}: pallas={got:.6f} xla={want:.6f} "
            f"rel_err={err:.3e} (tol {tol:g})")
        check(err <= tol, f"step-1 {key} disagrees with the XLA step")
    tr.close()


def phase_serve(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import apply_model, init_model
    from repro.serve.engine import InferenceEngine, Request

    params, kstate = init_model(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens, news = SERVE_PROMPTS, SERVE_NEW
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, news))]
    eng = InferenceEngine(cfg, params, kstate, max_slots=4,
                          max_len=max(lens) + max(news), record_logits=True)
    say(f"[serve] attn_backends={eng.attn_backends}")
    check(all("pallas_paged" in b for b in eng.attn_backends.values()),
          "decode does not resolve to the paged-decode kernel")
    t0 = time.perf_counter()
    out = eng.run(reqs)
    dt = time.perf_counter() - t0
    for r in reqs:
        say(f"[serve] request {r.uid}: prompt {r.prompt_len} -> "
            f"{len(out[r.uid])}/{r.max_new_tokens} tokens")
        check(len(out[r.uid]) == r.max_new_tokens,
              f"request {r.uid} stopped early")
    say(f"[serve] wall_s={dt:.1f} for {len(reqs)} requests, compiles "
        f"included (host clock)")
    eng.close()

    # Prefill logits against the full forward through impl="xla". In
    # bfloat16 the two differ by more than rounding: balanced top-k
    # membership flips wherever a rounding moves a near tie, and a flip
    # reroutes a token. So the compared run is the same engine on the
    # same weights in float32, every matmul (kernels included) at
    # precision="highest", where nothing but summation order differs.
    # The bfloat16 distances are printed for the record.
    from repro.configs.base import with_overrides
    probe = reqs[1]
    V = cfg.vocab_size
    toks = jnp.asarray(probe.prompt, jnp.int32)[None]
    cfg32 = with_overrides(cfg, dtype="float32")
    params32 = jax.tree.map(
        lambda x: (x.astype(jnp.float32)
                   if jnp.issubdtype(x.dtype, jnp.floating) else x), params)

    def xla_logits(c, p, precision):
        def f(p, k, t):
            return apply_model(p, k, {"tokens": t}, c, update_state=False,
                               impl="xla")[0][0, -1]
        with jax.default_matmul_precision(precision):
            return np.asarray(jax.jit(f)(p, kstate, toks), np.float32)[:V]

    def engine_logits(e):
        return np.asarray(e.logits_trace[probe.uid][0],
                          np.float32).reshape(-1)[:V]

    with jax.default_matmul_precision("highest"):
        eng32 = InferenceEngine(cfg32, params32, kstate, max_slots=1,
                                max_len=probe.prompt_len + 4,
                                record_logits=True)
        out32 = eng32.run([Request(uid=probe.uid, prompt=probe.prompt,
                                   max_new_tokens=4)])
        eng32.close()
    check(len(out32[probe.uid]) == 4, "float32 engine stopped early")
    ref32 = xla_logits(cfg32, params32, "highest")
    got32, got16 = engine_logits(eng32), engine_logits(eng)
    xla16 = xla_logits(cfg, params, "default")
    err = rel_err(got32, ref32)
    say(f"[serve] bfloat16 prefill logits of request {probe.uid} vs the "
        f"float32 XLA forward (record only): engine {rel_err(got16, ref32):.3e}"
        f", XLA {rel_err(xla16, ref32):.3e}, engine vs XLA "
        f"{rel_err(got16, xla16):.3e}")
    say(f"[serve] float32 prefill logits of request {probe.uid} "
        f"({probe.prompt_len} tokens), engine vs XLA forward: rel_err="
        f"{err:.3e} (tol {PREFILL_RTOL:g}); argmax {int(got32.argmax())} "
        f"vs {int(ref32.argmax())}")
    check(err <= PREFILL_RTOL, "engine prefill logits disagree with the "
          "XLA forward")


def _fit_losses(run, mesh, seed):
    """Losses of TRAIN_STEPS launcher steps of ``run`` on ``mesh``."""
    from repro.launch.train import make_sharded_step
    from repro.train.trainer import Trainer
    step, ts_spec = make_sharded_step(run, mesh)
    tr = Trainer(run, _loader(run, seed), mesh=mesh, shardings=ts_spec,
                 step_fn=step)
    t0 = time.perf_counter()
    tr.fit(TRAIN_STEPS)
    losses = [float(h["loss"]) for h in tr.metrics_history]
    tr.close()
    return losses, time.perf_counter() - t0


def phase_four_chips(cfg, seed: int) -> None:
    import jax

    from repro.configs.base import with_overrides
    from repro.launch.distributed import make_process_mesh
    from repro.launch.mesh import auto_mesh
    B = 4
    # Dropout is off: a step draws its mask over whatever batch a program
    # holds, so masks would differ between these layouts by construction.
    # Depth is cut: every layer exercises the mesh, the collectives and
    # the sharded state the same way.
    cfg = with_overrides(cfg, dropout=0.0, num_layers=FOUR_CHIP_LAYERS)
    say(f"[4chip] {cfg.name} at full width, {cfg.num_layers} of 12 "
        f"layers, batch {B} x seq {SEQ}, float32")
    one = auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    ref, dt = _fit_losses(_train_run(cfg, B), one, seed)
    say(f"[4chip] one device: losses={[round(x, 5) for x in ref]} "
        f"wall_s={dt:.1f}")
    check(all(math.isfinite(x) for x in ref), "non-finite reference loss")
    for name, shape, kw, tol in (
            ("gspmd", (2, 2), {}, GSPMD_LOSS_RTOL),
            ("int8_ef", (4, 1), {"grad_compression": "int8_ef"},
             INT8_LOSS_RTOL)):
        mesh = make_process_mesh(*shape)
        check(dict(mesh.shape) == {"data": shape[0], "model": shape[1]},
              f"{name}: got mesh {dict(mesh.shape)}")
        losses, dt = _fit_losses(_train_run(cfg, B, **kw), mesh, seed)
        errs = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
        say(f"[4chip] {name} mesh {dict(mesh.shape)}: "
            f"losses={[round(x, 5) for x in losses]} "
            f"max_rel_err={max(errs):.3e} (tol {tol:g}) wall_s={dt:.1f}")
        check(len(losses) == TRAIN_STEPS, f"{name}: ran {len(losses)} steps")
        check(max(errs) <= tol, f"{name} losses disagree with one device")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke.py must run from a checkout of the repository "
              f"(no {src}/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import use_compile_cache
    say(f"[setup] compile cache: {use_compile_cache()}")

    try:
        device = phase_device(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.configs.base import with_overrides
    cfg = get_config(CFG_NAME)
    train_cfg = with_overrides(cfg, dtype="float32")
    if args.chips == 4:
        phases = [("4chip", lambda: phase_four_chips(train_cfg, args.seed))]
    else:
        phases = [("kernels", lambda: phase_kernels(train_cfg, args.seed)),
                  ("train", lambda: phase_train(train_cfg, args.seed)),
                  ("serve", lambda: phase_serve(cfg, args.seed))]
    # every phase runs, so one run reports every fault; any failure makes
    # the exit code non-zero and withholds the result line
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:
            failed.append(name)
            print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr)
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            continue
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
