"""The paper's headline claim on your CPU: routing attention is
O(n^1.5 d) while full attention is O(n^2 d).

Runs one attention layer at growing sequence lengths and prints measured
wall time + the FLOPs model; the routing curve grows ~n^1.5, full ~n^2.

Run:  PYTHONPATH=src python examples/long_context.py
"""
import math
import time

import jax
import jax.numpy as jnp

from repro.configs.base import RoutingConfig
from repro.core.attention import full_attention
from repro.core.kmeans import init_kmeans
from repro.core.routing import routed_attention
from repro.launch.compile_cache import use_compile_cache


def bench(fn, *args, reps=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def main():
    use_compile_cache()
    B, H, dh = 1, 4, 64
    print(f"{'n':>7} {'k=sqrt(n)':>9} {'full ms':>9} {'routing ms':>11} "
          f"{'speedup':>8}")
    full_t = {}
    for n in (1024, 2048, 4096, 8192):
        ks = jax.random.split(jax.random.PRNGKey(n), 2)
        q = jax.random.normal(ks[0], (B, H, n, dh))
        v = jax.random.normal(ks[1], (B, H, n, dh))
        k_clusters = 2 ** round(math.log2(math.sqrt(n)))
        st = init_kmeans(jax.random.PRNGKey(0), H, k_clusters, dh)
        cfg = RoutingConfig(num_clusters=k_clusters)

        f_full = jax.jit(lambda q, v: full_attention(q, q, v, causal=True,
                                                     chunk=1024))
        f_rout = jax.jit(lambda q, v, mu: routed_attention(
            q, None, v, type(st)(mu=mu), cfg, update_state=False).out)
        t_full = bench(f_full, q, v)
        t_rout = bench(f_rout, q, v, st.mu)
        full_t[n] = t_full
        print(f"{n:>7} {k_clusters:>9} {t_full*1e3:>9.1f} "
              f"{t_rout*1e3:>11.1f} {t_full/t_rout:>7.1f}x")
    # scaling exponents from the two endpoints
    ns = sorted(full_t)
    print("\nfull-attention time scaling exponent "
          f"(expect ~2): "
          f"{math.log(full_t[ns[-1]]/full_t[ns[0]])/math.log(ns[-1]/ns[0]):.2f}")


if __name__ == "__main__":
    main()
