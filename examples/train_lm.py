"""End-to-end training driver: any `--arch` from the registry, with
checkpoint/restart, preemption handling, and straggler monitoring.

Default trains a ~100M-param Routing Transformer (the paper's PG-19
architecture at reduced width) for a few hundred steps on the synthetic
Markov stream. Kill it mid-run and re-run the same command: it resumes
from the last checkpoint bit-exactly.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
      PYTHONPATH=src python examples/train_lm.py --arch qwen2-0.5b --reduced
"""
import argparse

from repro.configs import ARCHS, get_config, reduced_config
from repro.configs.base import (RunConfig, TrainConfig, with_overrides,
                                RoutingConfig, ModelConfig)
from repro.data.synthetic import SyntheticLoader
from repro.train.trainer import Trainer
from repro.launch.compile_cache import use_compile_cache


def default_100m() -> ModelConfig:
    # pg19-shaped Routing Transformer, ~100M params, CPU-trainable
    return ModelConfig(
        name="rt-100m", family="dense", num_layers=8, d_model=512,
        num_heads=8, num_kv_heads=8, d_ff=2048, vocab_size=32000,
        attention="local+routing",
        routing=RoutingConfig(num_clusters=8, local_window=128,
                              routing_heads=2, routing_layers=(6, 7)),
        attn_window=128, position="rope", dtype="float32")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rt-100m",
                    choices=["rt-100m"] + sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduction of --arch")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    if args.arch == "rt-100m":
        cfg = default_100m()
    elif args.reduced:
        cfg = reduced_config(args.arch)
    else:
        cfg = get_config(args.arch)
    cfg = with_overrides(cfg, dtype="float32")
    run = RunConfig(model=cfg, train=TrainConfig(
        global_batch=args.batch, seq_len=args.seq, steps=args.steps,
        lr=2e-4 if cfg.param_count() > 5e7 else 1e-3,
        schedule="linear_warmup_rsqrt", warmup_steps=100,
        optimizer="adam", remat="full"))
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"steps={args.steps} ckpt={args.ckpt_dir}")

    loader = SyntheticLoader("markov", min(cfg.vocab_size, 512),
                             args.batch, args.seq)
    tr = Trainer(run, loader, ckpt_dir=args.ckpt_dir,
                 ckpt_every=args.ckpt_every,
                 on_straggler=lambda s, r: print(
                     f"  [straggler] step {s} was {r:.1f}x median"))
    tr.init_or_restore()
    start = int(tr.state.step)
    if start:
        print(f"resumed from checkpoint at step {start}")
    out = tr.fit(args.steps)
    hist = tr.metrics_history
    if hist:
        print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
              f"over {len(hist)} steps "
              f"(median step {sorted(h['step_time_s'] for h in hist)[len(hist)//2]*1e3:.0f} ms)")
    print(f"done: {out}")


if __name__ == "__main__":
    main()
