"""Shared plumbing for the Pallas kernels.

* ``default_interpret``: the platform-derived Pallas interpret default.
  Kernels compile with Mosaic only on TPU; everywhere else (CPU CI, the
  dev container) they run in interpret mode with identical semantics.
  Callers that pass ``interpret=None`` get the derived default, so a
  call site that forgets ``interpret=False`` on TPU cannot silently
  benchmark interpret mode (and a CPU caller cannot crash into Mosaic).
* ``float0_like``: custom-VJP cotangents for integer operands (membership
  indices, positions). jax requires ``float0`` for int-dtype primals.
* ``FUSED_RESIDENT_BYTES`` / ``fused_paged_default`` /
  ``fused_vmem_limit``: the shared rule for when the fused routing kernel
  keeps whole (N, dh) sequence planes resident in VMEM vs streams member
  rows from HBM, and the VMEM limit each plan compiles with;
  ``fused_cluster_bytes`` / ``FUSED_CLUSTER_BYTES``: what one cluster's
  rows and blocks take of that limit, and the most they may. The plan
  is chosen here alone, from N·dh; no caller selects it.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

NEG = -1e9

# VMEM budget for the fused routing kernel's resident planes. The plan
# holds each (N, dh) float32 plane of the current batch·head, at
# row_lanes(dh), as a pipelined input block, which the pipeline
# double-buffers. At rt-enwik8 widths (N=8192, dh=128) that is 16 MiB
# shared-QK (q, v) and 24 MiB with separate keys — both resident; so is
# rt-imagenet64's N=12288, dh=64 (24 MiB at 128 lanes); N=16384 pages.
# v5e has 128 MiB of VMEM; the compile, not this constant, is the final
# word (tests/test_tpu_compile).
FUSED_RESIDENT_BYTES = 24 << 20
# room around the planes for the per-cluster row buffers and pipelined
# blocks (fused_cluster_bytes: 1.5 MiB at w = 256, dh = 128, shared QK)
# and the temporaries of the kernel body's unrolled sub-tiles. At
# w = 1536, dh = 64 (rt-imagenet64: 144 sub-tile pairs) the paged plan
# needs 22.5 MB of scoped VMEM to compile for v5e (tests/test_tpu_compile)
_TILE_HEADROOM_BYTES = 24 << 20
# the share of that room one cluster's buffers and blocks may take
FUSED_CLUSTER_BYTES = 12 << 20


def row_lanes(dh: int) -> int:
    """Lanes a float32 row of ``dh`` takes in VMEM: the 128-lane tile,
    rounded up. The fused routing kernel's member rows and planes are
    this wide (dh = 64 rows are zero-padded to 128)."""
    return -(-dh // 128) * 128


def fused_resident_bytes(n: int, dh: int, planes: int) -> int:
    """VMEM bytes the resident plan's ``planes`` (N, dh) float32 planes
    take, double-buffered, at ``row_lanes(dh)``."""
    return planes * 2 * n * row_lanes(dh) * 4


def fused_cluster_bytes(w: int, dh: int, planes: int) -> int:
    """VMEM bytes of the fused routing kernels' per-cluster buffers and
    blocks, the backward's (the larger): two slots of w member rows a
    plane, and the pipeline's double-buffered do block, three gradient
    blocks, row stats and member positions; every (w, dh) row block at
    ``row_lanes(dh)``."""
    rows = w * row_lanes(dh) * 4
    return (planes * 2 + 2 * (1 + 3)) * rows + 2 * 4 * w * 4


def fused_paged_default(n: int, dh: int, planes: int,
                        paged: Optional[bool] = None) -> bool:
    """Resolve a ``paged`` argument for the fused routing kernel: None
    (the program's only value) pages exactly when the resident planes
    would exceed ``FUSED_RESIDENT_BYTES``; an explicit bool (a test
    seam) wins."""
    if paged is None:
        return fused_resident_bytes(n, dh, planes) > FUSED_RESIDENT_BYTES
    return bool(paged)


def fused_vmem_limit(n: int, dh: int, planes: int, resident: bool) -> int:
    """``vmem_limit_bytes`` for a fused routing kernel call."""
    return ((fused_resident_bytes(n, dh, planes) if resident else 0)
            + _TILE_HEADROOM_BYTES)


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` argument: None derives from the backend
    the program runs on (compiled on TPU, interpret elsewhere); an
    explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def float0_like(x):
    """Zero cotangent for an integer-dtype primal (custom_vjp bwd)."""
    return np.zeros(np.shape(x), jax.dtypes.float0)
