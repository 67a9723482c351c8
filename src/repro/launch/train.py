"""Distributed training launcher: mesh + sharding rules + Trainer.

Multi-host: each host runs this module once; coordinator discovery is
env/flag-driven (launch/distributed.py, DESIGN.md §7). Single-process
runs — laptops, CI — take the same path through the no-op fallback (use
XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise the
sharded path on CPU).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 100 --mesh 2x4

  # int8 error-feedback gradient compression (data-parallel shard_map)
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 100 --grad-compression int8_ef

  # two-host launch (per host; coordinator = host 0)
  REPRO_COORDINATOR=host0:9876 REPRO_NUM_PROCESSES=2 REPRO_PROCESS_ID=$RANK \
      python -m repro.launch.train --arch granite-8b --mesh 8x2
"""
from __future__ import annotations

import argparse
import functools

from repro.launch import distributed


def make_sharded_step(run, mesh, seq_parallel: bool = False):
    """The launcher's train step on ``mesh``: GSPMD (data, model) rules
    with activation constraints, or the int8_ef shard_map step. Returns
    ``(step, ts_spec)``: ``step(ts, batch)`` commits the batch to its
    data sharding and runs the jitted step (state donated, output pinned
    to ``ts_spec`` so it round-trips into the next step); ``step.jitted``
    is the jitted function, for ``.lower(...)`` inspection."""
    import jax

    from repro.attn import specs_for_model
    from repro.dist import sharding as shd
    from repro.train.train_step import init_train_state, make_train_step

    cfg = run.model
    compressed = run.train.grad_compression == "int8_ef"
    use_fsdp = cfg.param_count() > 20e9
    ts_shapes = jax.eval_shape(
        functools.partial(init_train_state, run, mesh=mesh),
        jax.random.PRNGKey(0))
    ts_spec = shd.train_state_sharding(mesh, ts_shapes, fsdp=use_fsdp)
    constrain = (None if compressed else shd.make_constrain_fn(
        mesh, seq_parallel, fsdp_prefetch=use_fsdp,
        attn_specs=specs_for_model(cfg)))
    fn = make_train_step(run, constrain_fn=constrain, mesh=mesh)

    def pinned_fn(ts, batch):
        # pin the output state to the rule layout so it round-trips into
        # the next step's in_shardings (GSPMD would otherwise pick its own
        # layout for unconstrained outputs, e.g. scanned norm scales)
        new_ts, metrics = fn(ts, batch)
        new_ts = jax.tree.map(jax.lax.with_sharding_constraint,
                              new_ts, ts_spec)
        return new_ts, metrics

    tc = run.train
    b_spec = shd.batch_sharding(mesh, {"tokens": jax.ShapeDtypeStruct(
        (tc.global_batch, tc.seq_len + 1), "int32")})
    jitted = jax.jit(pinned_fn, in_shardings=(ts_spec, b_spec),
                     donate_argnums=(0,))

    def sharded_step(ts, batch):
        return jitted(ts, jax.device_put(batch, b_spec))

    sharded_step.jitted = jitted
    return sharded_step, ts_spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="", help="DxM, e.g. 2x4 (default: "
                                               "all devices as data)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--grad-compression", default="none",
                    help="one of configs.base.GRAD_COMPRESSION_MODES; "
                         "int8_ef: error-feedback int8 gradient exchange "
                         "(data-parallel shard_map path); validated by "
                         "TrainConfig after the deferred imports")
    ap.add_argument("--obs-jsonl", default=None,
                    help="append per-step metric records (schema v1 JSONL, "
                         "validated by `python -m repro.obs.schema`)")
    ap.add_argument("--routing-stats", action="store_true",
                    help="compute routing-health telemetry (occupancy "
                         "entropy, dead clusters, centroid drift, sampled "
                         "attention recall) inside the jitted step; off by "
                         "default — stats-off compiles byte-identical HLO")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax profiler trace of the whole run "
                         "into this directory (TensorBoard/Perfetto)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (or $REPRO_COORDINATOR)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total launched processes (or $REPRO_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank (or $REPRO_PROCESS_ID)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # before any jax backend use: registers the global device view
    multi = distributed.initialize(coordinator=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)

    import jax
    import jax.numpy as jnp  # noqa: F401  (kept for parity with examples)

    from repro.configs import ARCHS, get_config, reduced_config
    from repro.configs.base import RunConfig, TrainConfig, with_overrides
    from repro.data.synthetic import SyntheticLoader
    from repro.train.trainer import Trainer

    if args.arch not in ARCHS:
        ap.error(f"unknown --arch {args.arch}; choices: {sorted(ARCHS)}")

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    cfg = with_overrides(cfg, dtype="float32")
    if args.routing_stats:
        cfg = with_overrides(
            cfg, routing=with_overrides(cfg.routing, stats=True))
    run = RunConfig(model=cfg, train=TrainConfig(
        global_batch=args.batch, seq_len=args.seq, steps=args.steps,
        lr=1e-3, schedule="linear_warmup_rsqrt", warmup_steps=20,
        grad_compression=args.grad_compression))

    n = jax.device_count()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = n, 1
    compressed = run.train.grad_compression == "int8_ef"
    if compressed and m > 1:
        ap.error("--grad-compression int8_ef is data-parallel only; "
                 "use --mesh Dx1")
    if compressed and args.seq_parallel:
        ap.error("--seq-parallel needs the GSPMD path; drop it or use "
                 "--grad-compression none")
    mesh = distributed.make_process_mesh(d, m)   # clamps oversubscription
    d, m = mesh.shape["data"], mesh.shape["model"]
    info = distributed.process_info()
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh=({d}x{m}) devices={n} "
          f"process={info['process_index']}/{info['process_count']} "
          f"multi_host={multi} compression={run.train.grad_compression}")

    sharded_step, ts_spec = make_sharded_step(run, mesh, args.seq_parallel)
    loader = SyntheticLoader("markov", min(cfg.vocab_size, 512),
                             args.batch, args.seq)
    from repro.obs import trace as obs_trace
    with mesh:
        tr = Trainer(run, loader, ckpt_dir=args.ckpt_dir, mesh=mesh,
                     shardings=ts_spec, step_fn=sharded_step,
                     obs_jsonl=args.obs_jsonl)
        tr.init_or_restore()   # fresh: sharded init; ckpt: elastic resume
        with obs_trace.profile(args.profile_dir):
            out = tr.fit(args.steps)
        tr.close()
    hist = tr.metrics_history
    if hist:
        print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    print(out)


if __name__ == "__main__":
    main()
