"""Training cells: the launcher's step (``launch.train.make_sharded_step``)
driven by ``train.trainer.Trainer.fit``, as ``python -m
repro.launch.train`` trains.

Set-up builds one trainer with its compiled step and a state made from
the seed (weights from the reference's generator, in the program's
layout and dtype), and drives it through its first steps on the first
batches of the pool. Those steps are the ones compared with the
reference. The same trainer then runs the window: as many further steps
as fill ``seconds``, judged by the last step's end, so the rate is all
the tokens of all the window's steps over all its time.

After the window the program's state is freed and the reference
(``refs/<reference>.py``) trains the same steps on the same batches from
the same seed. Three numbers are compared, each against its limit in
``limits/<workload>.json``:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: step 1's clipped gradient, as the optimizer holds it
  (Adam's first moment over ``1 - beta1``), by the worst leaf: the gap
  between the program's leaf norm and the reference's, over the larger
  of the reference's leaf norm and its median leaf norm;
* ``update_gap``: the same for the weights' change over all the
  compared steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (Adam moves those by round-off alone).
"""
from __future__ import annotations

import gc
import importlib
import statistics
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.routing_lm import seed_key

CHECKED_STEPS = 3
TRACED_STEPS = 3
# a leaf whose reference gradient is under this share of the median
# leaf's is left out of update_gap
STILL_LEAF = 1e-3

# canonical leaf name -> (path in the program's params tree)
LEAVES = {
    "tok": ("embed", "tok"), "unembed": ("embed", "unembed"),
    "lnf_scale": ("final_norm", "scale"), "lnf_bias": ("final_norm", "bias"),
    "ln1_scale": ("ln1", "scale"), "ln1_bias": ("ln1", "bias"),
    "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"),
    "wo": ("attn", "wo"),
    "ln2_scale": ("ln2", "scale"), "ln2_bias": ("ln2", "bias"),
    "w_up": ("ffn", "w_up"), "w_down": ("ffn", "w_down"),
}


def program_config(c: dict):
    """The program's ModelConfig for configuration dict ``c``."""
    from repro.configs.base import ModelConfig, RoutingConfig
    return ModelConfig(
        name="bench", family="dense", num_layers=c["num_layers"],
        d_model=c["d_model"], num_heads=c["num_heads"],
        num_kv_heads=c["num_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_seq_len"],
        attention="local+routing",
        routing=RoutingConfig(num_clusters=c["num_clusters"],
                              local_window=c["local_window"],
                              routing_heads=c["routing_heads"],
                              decay=c["decay"], share_qk=True),
        attn_window=c["local_window"], position="rope",
        rope_theta=c["rope_theta"], norm="layernorm", act="relu",
        dropout=c["dropout"], dtype=c["dtype"])


def program_params(p: dict, mu, dtype):
    """Canonical weights -> the program's (params, kstate) trees."""
    cast = lambda a: a.astype(dtype)
    layer = {}
    for name, (group, leaf) in LEAVES.items():
        if group not in ("embed", "final_norm"):
            layer.setdefault(group, {})[leaf] = cast(p[name])
    params = {"embed": {"tok": cast(p["tok"]), "unembed": cast(p["unembed"])},
              "final_norm": {"scale": cast(p["lnf_scale"]),
                             "bias": cast(p["lnf_bias"])},
              "stack": [(layer,)]}
    return params, [{"0": mu.astype(jnp.float32)}]


def canonical(params) -> Dict[str, jax.Array]:
    """The program's params tree -> canonical leaf dict."""
    out = {}
    for name, (group, leaf) in LEAVES.items():
        src = (params[group] if group in ("embed", "final_norm")
               else params["stack"][0][0][group])
        out[name] = src[leaf]
    return out


class PoolLoader:
    """Cycles through a pool of device batches (the Trainer's loader
    protocol: iterator plus ``state``/``restore``)."""

    def __init__(self, pool):
        self.pool = pool
        self.step = 0
        self.drawn = []         # host clock at each draw: a step's start

    def state(self):
        return {"step": self.step}

    def restore(self, st):
        self.step = int(st["step"])

    def __iter__(self):
        return self

    def __next__(self):
        self.drawn.append(time.perf_counter())
        b = self.pool[self.step % len(self.pool)]
        self.step += 1
        return {"tokens": b}


def gap(prog: Dict[str, float], ref: Dict[str, float], leaves) -> float:
    """Worst leaf of |prog - ref| / max(ref leaf, median ref leaf)."""
    med = statistics.median(ref[n] for n in leaves)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in leaves)


def run(ctx, fault: Optional[Callable] = None) -> dict:
    """One run of a training cell. ``ctx`` is the harness's RunContext;
    ``fault`` (tests and fault readings only) wraps the program's step
    function."""
    from repro.launch.mesh import auto_mesh
    from repro.launch.train import make_sharded_step
    from repro.optim import make_optimizer
    from repro.configs.base import RunConfig, TrainConfig
    from repro.dist.sharding import batch_sharding
    from repro.train.train_step import TrainState, init_train_state
    from repro.train.trainer import Trainer

    c, t, traffic = ctx.config["model"], ctx.config["train"], ctx.traffic
    ref = importlib.import_module(f"bench.refs.{ctx.config['reference']}")
    kind = importlib.import_module(f"bench.traffic.kinds.{traffic['kind']}")
    chips, devs = ctx.chips, ctx.devices[:ctx.chips]
    rows = traffic["rows_per_chip"] * chips
    n = traffic["seq_len"]
    dtype = jnp.dtype(c["dtype"])
    train_seed = t["seed"]
    key = seed_key(ctx.seed)

    cfg = program_config(c)
    run_cfg = RunConfig(model=cfg, train=TrainConfig(
        global_batch=rows, seq_len=n, steps=1 << 30,
        optimizer=t["optimizer"], lr=t["lr"], betas=tuple(t["betas"]),
        eps=t["eps"], grad_clip=t["grad_clip"], schedule=t["schedule"],
        warmup_steps=t["warmup_steps"], remat=t["remat"], seed=train_seed))
    mesh = auto_mesh((chips, 1), ("data", "model"), devices=devs)
    step, ts_spec = make_sharded_step(run_cfg, mesh)
    if fault is not None:
        step = fault(step)
    opt_init = make_optimizer(run_cfg.train)[0]

    def initial_state(k):
        p, mu = ref.init_params(k, c)
        params, kstate = program_params(p, mu, dtype)
        return TrainState(params, kstate, opt_init(params),
                          jnp.zeros((), jnp.int32), None)

    want = jax.tree.structure(jax.eval_shape(
        lambda k: init_train_state(run_cfg, k, mesh=mesh),
        jax.random.PRNGKey(0)))
    got = jax.tree.structure(jax.eval_shape(initial_state, key))
    if want != got:
        raise RuntimeError(f"the program's train state is laid out as "
                           f"{want}, the benchmark builds {got}")
    with mesh:
        state = jax.jit(initial_state, out_shardings=ts_spec)(key)
    b_spec = batch_sharding(mesh, {"tokens": jax.ShapeDtypeStruct(
        (rows, n + 1), jnp.int32)})["tokens"]
    pool = kind.make(traffic, chips, jax.random.fold_in(key, 1), b_spec)
    ctx.note(f"[setup] state and batches made: {ctx.since_start():.1f} s")

    loader = PoolLoader(pool)
    tr = Trainer(run_cfg, loader, mesh=mesh, shardings=ts_spec,
                 step_fn=step)
    tr.state = state
    del state
    b1 = t["betas"][0]
    grad_of_m = jax.jit(lambda m: {k: v / (1.0 - b1) for k, v in
                                   canonical(m).items()})
    tr.fit(1)
    ctx.note(f"[setup] first step done: {ctx.since_start():.1f} s")
    g_prog = jax.device_get(ref.leaf_norms(grad_of_m(tr.state.opt_state["m"])))
    t1 = time.perf_counter()
    tr.fit(CHECKED_STEPS)
    t_step = (time.perf_counter() - t1) / (CHECKED_STEPS - 1)
    losses = [float(h["loss"]) for h in tr.metrics_history[:CHECKED_STEPS]]
    p0 = jax.jit(lambda k: {n_: a.astype(dtype) for n_, a in
                            ref.init_params(k, c)[0].items()})(key)
    dp_prog = jax.device_get(ref.leaf_gap_norms(canonical(tr.state.params),
                                                p0))
    del p0

    steps = TRACED_STEPS if ctx.trace else max(
        1, round(ctx.seconds / max(t_step, 1e-9)))
    start = int(tr.state.step)
    ctx.compiles.reset()
    with ctx.window():
        t0 = time.perf_counter()
        tr.fit(start + steps)
        jax.block_until_ready(tr.state.params)
        t_end = time.perf_counter()
    window_compiles = ctx.compiles.count
    done = int(tr.state.step) - start
    # each step ends in the trainer's metric fetch, so draw to draw (and
    # the last draw to the window's end) is a step's wall time
    marks = loader.drawn[-done:] + [t_end]
    walls = sorted(b - a for a, b in zip(marks, marks[1:]))
    ctx.note(f"[window] {done} steps; step wall time median "
             f"{walls[len(walls) // 2]:.4f} s, slowest "
             f"{[round(w, 4) for w in walls[-3:]]}")
    window_s = t_end - t0
    tokens = done * rows * n
    hist = tr.metrics_history[start:]
    failed = sum(1 for h in hist if not np.isfinite(float(h["loss"])))
    memory_peak = ctx.memory_peak()

    # free the program's state before the reference runs
    tr.state = None
    del tr, step
    gc.collect()

    ref_batches = [jax.device_put(np.asarray(b), devs[0])
                   for b in pool[:CHECKED_STEPS]]
    with jax.default_device(devs[0]):
        ref_losses, g_ref, dp_ref = ref.train(
            jax.device_put(key, devs[0]), ref_batches, c, t, train_seed)
    leaves = sorted(g_ref)
    g_med = statistics.median(g_ref[n_] for n_ in leaves)
    moving = [n_ for n_ in leaves if g_ref[n_] >= STILL_LEAF * g_med]
    checks = {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref_losses)),
        "grad_gap": float(gap(g_prog, g_ref, leaves)),
        "update_gap": float(gap(dp_prog, dp_ref, moving)),
    }
    ctx.note(f"[train] losses program {losses} reference {ref_losses}")
    ctx.note(f"[train] still leaves left out of update_gap: "
             f"{sorted(set(leaves) - set(moving))}")
    return {
        "metrics": {"train_tokens_per_s": tokens / window_s,
                    "setup_s": ctx.setup_s(t0)},
        "checks": checks,
        "attempted": done,
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "window_compiles": window_compiles,
        "layer_ctx": {"mode": "train", "config": c, "traffic": traffic,
                      "chips": chips, "steps": done,
                      "rows_per_chip": traffic["rows_per_chip"],
                      "tokens": tokens, "window_s": window_s,
                      "elem_bytes": dtype.itemsize},
    }
