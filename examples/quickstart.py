"""Quickstart: train a tiny Routing Transformer (half local heads, half
content-routed heads, per the paper) on a synthetic Markov language and
generate from it with the cluster-paged serving cache.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import (ModelConfig, RoutingConfig, RunConfig,
                                TrainConfig)
from repro.data.synthetic import SyntheticLoader
from repro.serve.serving import init_cache, make_serve_step, prefill
from repro.train.train_step import init_train_state, make_train_step
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    cfg = ModelConfig(
        name="rt-quickstart", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=64,
        attention="local+routing",
        routing=RoutingConfig(num_clusters=4, local_window=16),
        dtype="float32")
    run = RunConfig(model=cfg, train=TrainConfig(
        global_batch=16, seq_len=64, steps=60, lr=3e-3, schedule="const",
        warmup_steps=5))

    print(f"model: {cfg.name}, {cfg.param_count()/1e3:.0f}K params, "
          f"{cfg.num_heads//2} local + {cfg.num_heads//2} routing heads")
    ts = init_train_state(run, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(run))
    loader = SyntheticLoader("markov", cfg.vocab_size, 16, 64)
    for i, batch in zip(range(run.train.steps), loader):
        ts, m = step(ts, {k: jnp.asarray(v) for k, v in batch.items()})
        if i % 10 == 0 or i == run.train.steps - 1:
            print(f"step {i:3d}  loss {float(m['loss']):.3f}  "
                  f"grad_norm {float(m['grad_norm']):.2f}")

    # --- generate: prefill a prompt, decode greedily with the
    # cluster-paged routing cache; a trained model should assign high
    # likelihood to its own continuations under the Markov transition table
    prompt = jnp.asarray(next(iter(loader))["tokens"][:1, :32])
    cache = init_cache(cfg, 1, max_len=96)
    logits, cache = prefill(ts.params, ts.kstate, cache,
                            {"tokens": prompt}, cfg)
    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(logits[:, -1], -1)
    out = [int(tok[0])]
    logp = []
    for t in range(prompt.shape[1], prompt.shape[1] + 16):
        lg, cache = serve(ts.params, ts.kstate, cache, tok,
                          jnp.array([t], jnp.int32))
        logp.append(float(jax.nn.log_softmax(lg)[0, int(jnp.argmax(lg))]))
        tok = jnp.argmax(lg, -1)
        out.append(int(tok[0]))
    print("prompt tail :", [int(x) for x in prompt[0, -8:]])
    print("generated   :", out)
    import numpy as np
    print(f"mean greedy logprob: {np.mean(logp):.2f} "
          f"(untrained would be ~{-np.log(cfg.vocab_size):.2f})")
    assert np.mean(logp) > -np.log(cfg.vocab_size) + 1.0


if __name__ == "__main__":
    main()
