"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick of the per-layer metrics: model FLOP/s
utilization and each kernel's share of its roofline. They count the work
a Routing Transformer step has to do, not what an implementation happens
to do: balanced clusters of ``w = N / k`` members, the causal half of
every attention block, shared query/key routing vectors. Recomputation
(rematerialization, the flash backward's re-derived probabilities), the
extra passes of high-precision matmuls, and copies made to widen or
gather operands are not counted, so a change to the implementation
leaves these numbers where they are.

A forward attention pair (one query, one key) costs ``4 * dh`` FLOPs: the
score and the weighted value. Its backward costs twice that (dS from dO
and V, dV, dQ, dK), so forward and backward together cost three forward
passes. ``c`` is a configuration's ``model`` dict (see ``configs/``).
"""
from __future__ import annotations


def param_count(c) -> int:
    """Parameters of a pre-norm local+routing LM with an untied head."""
    d, f, V, L = c["d_model"], c["d_ff"], c["vocab_size"], c["num_layers"]
    hd = c["num_heads"] * c["head_dim"]
    per_layer = 3 * d * hd + hd * d + 2 * d * f + 4 * d
    return 2 * V * d + 2 * d + L * per_layer


def local_pairs_per_token(n: int, window: int) -> float:
    """Mean keys a query attends in causal blocked local attention: its
    own block up to itself, plus the whole previous block."""
    nb = n // window
    own = window * (window + 1) / 2 * nb
    prev = window * window * (nb - 1)
    return (own + prev) / n


def routing_pairs_per_token(n: int, clusters: int) -> float:
    """Mean keys per token in causal attention inside ``clusters``
    balanced clusters of ``w = n / clusters`` members."""
    w = n // clusters
    return clusters * w * (w + 1) / 2 / n


def attention_flops_per_token(c, n: int, backward: bool = True) -> float:
    """Attention FLOPs per token over all layers: the local and routing
    pairs, and the routing affinities (forward only: the centroids take
    no gradient)."""
    dh, hr = c["head_dim"], c["routing_heads"]
    hl = c["num_heads"] - hr
    pairs = (hl * local_pairs_per_token(n, c["local_window"])
             + hr * routing_pairs_per_token(n, c["num_clusters"]))
    fwd = 4 * dh * pairs
    affinity = 2 * c["num_clusters"] * dh * hr
    return c["num_layers"] * (fwd * (3 if backward else 1) + affinity)


def train_flops_per_token(c, n: int) -> float:
    """Model FLOPs per trained token: 6 per parameter (forward and
    backward matmuls) plus attention, no recomputation."""
    return 6 * param_count(c) + attention_flops_per_token(c, n)


def routing_kernel_counts(c, n: int, sequences: int, elem_bytes: int):
    """FLOPs and bytes of the fused routing attention kernels of one
    training step over ``sequences`` rows of ``n`` tokens, summed over
    layers and routing heads: forward (read r and v, write the
    per-cluster outputs and log-sum-exps) and backward (read r, v, the
    outputs, their cotangents and the log-sum-exps; write dr and dv).
    Membership indices are int32. Returns (flops, bytes)."""
    dh, k = c["head_dim"], c["num_clusters"]
    w = n // k
    calls = sequences * c["num_layers"] * c["routing_heads"]
    pairs = k * w * (w + 1) / 2
    flops = 3 * 4 * dh * pairs
    plane = n * dh * elem_bytes            # r or v in sequence layout
    blocks = k * w * dh * elem_bytes       # per-cluster output or grad
    rows = k * w * 4                       # one int32/float32 per member
    fwd = 2 * plane + blocks + 2 * rows    # + indices, lse
    bwd = 2 * plane + 2 * blocks + 2 * rows + 2 * plane
    return calls * flops, calls * (fwd + bwd)


def local_keys_at(t: int, window: int) -> int:
    """Keys a token at position ``t`` attends in blocked local attention
    (itself, the earlier part of its block, the whole previous block)."""
    return t - max(0, (t // window - 1) * window) + 1


def page_keys_at(t: int, clusters: int, cap: int) -> float:
    """Keys a decoded token at position ``t`` attends in its cluster's
    page: the earlier tokens of its cluster, ``t / clusters`` when
    clusters fill evenly, at most ``cap``, and itself."""
    return min(cap, t / clusters) + 1


def decode_flops(c, positions, cap: int) -> float:
    """Forward FLOPs of decoding one token at each of ``positions``: 2
    per parameter, the local and page attention pairs, and the routing
    affinities, over all layers."""
    dh, hr = c["head_dim"], c["routing_heads"]
    hl = c["num_heads"] - hr
    pairs = sum(hl * local_keys_at(t, c["local_window"])
                + hr * page_keys_at(t, c["num_clusters"], cap)
                for t in positions)
    per_token = 2 * param_count(c) + c["num_layers"] * (
        2 * c["num_clusters"] * dh * hr)
    return len(positions) * per_token + c["num_layers"] * 4 * dh * pairs


def paged_decode_counts(c, positions, cap: int, elem_bytes: int):
    """FLOPs and bytes of the paged routing decode kernel for one token
    at each of ``positions``, over layers and routing heads: read the
    page's occupied keys and values, the query and the token's value,
    write the output. Returns (flops, bytes)."""
    dh, L, hr = c["head_dim"], c["num_layers"], c["routing_heads"]
    keys = sum(page_keys_at(t, c["num_clusters"], cap) for t in positions)
    flops = L * hr * 4 * dh * keys
    nbytes = L * hr * elem_bytes * dh * (2 * (keys - len(positions))
                                         + 3 * len(positions))
    return flops, nbytes
