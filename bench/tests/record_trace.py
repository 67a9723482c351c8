"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs, on the chip, three calls of a jitted function holding the fused
routing attention kernel (shared-QK, 4 heads of 128, 2048 tokens in 32
clusters) and a matmul, inside the ``bench/window`` host span, with a
host-side pause between calls so the trace has idle gaps. Copies the
``.xplane.pb`` into ``out_dir``.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out_dir: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro.core.kmeans import cluster_scores, normalize_routing
    from repro.core.routing import balanced_topk
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    n, dh, kc, h = 2048, 128, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    r = normalize_routing(jax.random.normal(ks[0], (1, h, n, dh)))
    v = jax.random.normal(ks[1], (1, h, n, dh))
    mu = jax.random.normal(ks[2], (h, kc, dh))
    idx = balanced_topk(cluster_scores(r, mu), n // kc)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    m = jax.random.normal(ks[3], (2048, 2048))

    @jax.jit
    def f(r, v, m):
        o = ops.routed_attention_fused(r, None, v, idx, idx, pos,
                                       causal=True, interpret=False)
        return o.sum() + (m @ m).sum()

    jax.block_until_ready(f(r, v, m))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench/window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("host/pause"):
                    time.sleep(0.002)
                jax.block_until_ready(f(r, v, m))
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    for p in Path(tmp).rglob("*.xplane.pb"):
        shutil.copy(p, Path(out_dir) / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
