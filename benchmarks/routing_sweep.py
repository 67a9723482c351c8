"""--routing-sweep: the XLA routing reference vs the fused routing kernel.

One row per (sequence length, impl) through the full ``routed_attention``
module (shared-QK causal, k = sqrt-ish clusters of window 256), measuring
tok/s of the jitted call and peak memory (XLA ``memory_analysis`` temp +
output bytes). Impls: the ``xla`` reference, which gathers (B,H,k,w,dh)
blocks, and the gather-free ``pallas_fused`` kernel, which chooses its
own memory plan from N·dh. Every row carries the device kind and whether
the kernel ran in interpret mode, so hardware and CI numbers are never
conflated in the trend line.

The record is written to ``BENCH_routing.json`` at the repo root (a CI
artifact, not committed) together with the analytic routing-vs-flash
roofline (benchmarks/roofline.py ``attention_roofline``), whose
predicted O(n^1.5)-vs-O(n^2) crossover carries the at-scale speed story
that CPU wall-clock cannot.

``check=True`` gates the sweep: the kernel's output must match the xla
reference at every N.

Interpret-mode caveat (CPU CI): the kernel rows execute the kernel body
via the interpreter, where its in-VMEM row pulls cost more wall-clock
than XLA's vectorized HBM gather — tok/s *inverts* relative to
hardware, and ``peak_mb`` counts the interpreter's buffers, not the
chip's. Neither is a device number; the on-chip benchmark (``bench/``)
measures the kernel.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import List, Tuple

import jax
import numpy as np

from repro.configs.base import RoutingConfig
from repro.core.kmeans import init_kmeans
from repro.core.routing import routed_attention

Row = Tuple[str, float, str]

B, H, DH = 1, 2, 64
WINDOW = 256
SEQ_LENS = (1024, 4096, 8192)
IMPLS = ("xla", "pallas_fused")
CHECK_TOL = 2e-4
JSON_PATH = Path(__file__).resolve().parents[1] / "BENCH_routing.json"


def _peak_bytes(compiled) -> int:
    try:
        m = compiled.memory_analysis()
        return int(m.temp_size_in_bytes + m.output_size_in_bytes)
    except Exception:                      # backend without the analysis
        return 0


def _device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def routing_sweep_rows(iters: int = 3, seq_lens=SEQ_LENS,
                       check: bool = False) -> Tuple[List[Row], dict]:
    from benchmarks.roofline import attention_roofline
    platform = jax.default_backend()
    interpret = platform != "tpu"
    device = _device_kind()
    rows: List[Row] = []
    record = {
        "shape": {"B": B, "H": H, "dh": DH, "window": WINDOW},
        "platform": platform,
        "device_kind": device,
        "interpret": interpret,
        "note": ("interpret-mode wall-clock (CPU): fused in-kernel row "
                 "pulls are interpreter-slow, so tok/s inverts vs "
                 "hardware, and peak_mb counts the interpreter's "
                 "buffers — the at-scale speed story is the analytic "
                 "crossover under 'roofline'"),
        "checked": bool(check),
        "points": [],
        # analytic routing-vs-flash model + predicted O(n^1.5) crossover
        "roofline": attention_roofline(),
    }
    for N in seq_lens:
        kc = max(2, N // WINDOW)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, N, DH))
        v = jax.random.normal(ks[1], (B, H, N, DH))
        st = init_kmeans(ks[2], H, kc, DH)
        cfg = RoutingConfig(num_clusters=kc)
        point = {"N": N, "clusters": kc, "device_kind": device,
                 "interpret": interpret, "impls": {}}
        ref_out = None
        for impl in IMPLS:
            fn = jax.jit(lambda q, v, impl=impl: routed_attention(
                q, None, v, st, cfg, update_state=False, impl=impl).out)
            # one AOT compile serves both memory_analysis and timing
            compiled = fn.lower(q, v).compile()
            peak = _peak_bytes(compiled)
            out = compiled(q, v)
            jax.block_until_ready(out)
            if impl == "xla":
                ref_out = out
            maxdiff = float(jax.numpy.abs(out - ref_out).max())
            if check and maxdiff >= CHECK_TOL:
                raise SystemExit(
                    f"routing sweep parity check failed: N={N} impl="
                    f"{impl} maxdiff {maxdiff:.2e} >= {CHECK_TOL:.0e}")
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(q, v))
                ts.append(time.perf_counter() - t0)
            us = float(np.median(ts) * 1e6)
            tok_s = B * N / (us / 1e6)
            rows.append((f"routing_sweep/N{N}:{impl}", us,
                         f"tok_s={tok_s:.0f};peak_mb={peak / 2**20:.1f};"
                         f"device={device};interpret={interpret}"))
            point["impls"][impl] = {"us_per_call": round(us, 1),
                                    "tok_s": round(tok_s),
                                    "peak_bytes": peak,
                                    "maxdiff_vs_xla": maxdiff}
        x = point["impls"]["xla"]
        f = point["impls"]["pallas_fused"]
        point["fused_speedup_tok_s"] = round(f["tok_s"] / x["tok_s"], 3)
        point["fused_peak_ratio"] = (
            round(f["peak_bytes"] / x["peak_bytes"], 3)
            if x["peak_bytes"] else None)
        record["points"].append(point)
    return rows, record


def write_json(record: dict, path: Path = JSON_PATH) -> None:
    path.write_text(json.dumps(record, indent=2) + "\n")


if __name__ == "__main__":
    import sys
    print("name,us_per_call,derived")
    all_rows, record = routing_sweep_rows(check="--check" in sys.argv[1:])
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")
    write_json(record)
