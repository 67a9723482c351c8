"""Continuous-batching serving with the slot-pooled routing KV cache.

Twelve requests with mixed prompt lengths, generation lengths, and sampling
settings arrive staggered over time. The engine admits each into a free
cache lane (FCFS + token budget), decodes every active lane in ONE jitted
step (cluster-paged routing cache: O(window + cap) per token), retires
finished requests, and reuses their lanes for later arrivals — no request
ever waits for a batch-mate to finish.

Run:  PYTHONPATH=src python examples/serve_batch.py
"""
import numpy as np

import jax

from repro.configs.base import ModelConfig, RoutingConfig
from repro.models.model import init_model
from repro.serve.engine import InferenceEngine, Request, SamplingParams
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    cfg = ModelConfig(
        name="rt-serve", family="dense", num_layers=4, d_model=256,
        num_heads=8, num_kv_heads=4, d_ff=512, vocab_size=1024,
        attention="local+routing",
        routing=RoutingConfig(num_clusters=8, local_window=32),
        dtype="float32")
    params, kstate = init_model(cfg, jax.random.PRNGKey(0))

    n_req, max_slots = 12, 4
    rng = np.random.RandomState(1)
    prompt_lens = (24, 48, 96, 192)
    gen_lens = (8, 16, 24, 32)
    requests = []
    for uid in range(n_req):
        sampling = (SamplingParams() if uid % 3 == 0 else
                    SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                                   seed=uid))
        requests.append(Request(
            uid=uid,
            prompt=rng.randint(0, cfg.vocab_size,
                               size=prompt_lens[uid % 4]).tolist(),
            max_new_tokens=gen_lens[(3 * uid + 1) % 4],
            sampling=sampling,
            arrival_step=2 * uid))
    max_len = max(prompt_lens) + max(gen_lens)
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params; "
          f"{n_req} staggered requests over {max_slots} slots "
          f"(max_len={max_len})")

    eng = InferenceEngine(cfg, params, kstate, max_slots=max_slots,
                          max_len=max_len, token_budget=4 * max_len)
    outputs = eng.run(requests)

    print(f"{'uid':>3} {'arrive':>6} {'slot':>4} {'prompt':>6} {'gen':>4} "
          f"{'ttft_ms':>8}  first tokens")
    for r in requests:
        st = eng.metrics.requests[r.uid]
        print(f"{r.uid:>3} {st.arrival_step:>6} {st.slot:>4} "
              f"{st.prompt_len:>6} {st.n_generated:>4} "
              f"{st.ttft_s*1e3:>8.0f}  {outputs[r.uid][:6]}")

    s = eng.metrics.summary()
    print(f"decode: {s['decode_tokens']} tokens in {s['decode_steps']} steps "
          f"({s['decode_tokens_per_s']:.0f} tok/s, "
          f"occupancy {s['mean_occupancy']:.2f}/{max_slots}); "
          f"prefill: {s['prefill_tokens']} tokens")


if __name__ == "__main__":
    main()
