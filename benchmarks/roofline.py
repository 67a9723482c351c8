"""Roofline analysis (§Roofline): three terms per (arch x cell x mesh).

Terms (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute_s    = analytic_FLOPs / (chips x 197e12)
  memory_s     = analytic_HBM_bytes_per_chip / 819e9
  collective_s = loop-aware HLO collective bytes_per_chip (weighted) / 50e9

Why analytic FLOPs/bytes instead of cost_analysis(): XLA's cost analysis
does NOT multiply while-loop (lax.scan) bodies by trip count, so a
48-layer scanned stack reports ~1/48th of its FLOPs; the CPU backend also
upcasts bf16 dots to f32, inflating bytes. The collective term CAN be
recovered exactly from HLO because the while nesting structure is visible
in the text (see repro.launch.dryrun.collective_bytes). The HLO-reported
flops are kept in the record for reference.

Analytic model (per step, global):
  matmul FLOPs        fwd = 2 * N_matmul_active * tokens;  train x3 (bwd),
                      +1 fwd if remat=full (recompute)  -> 8NT counted in
                      `expected`, while MODEL_FLOPS (the "useful" number)
                      stays 6NT per the task spec.
  attention FLOPs     per attn layer fwd = 4*B*S*K_eff*H*dh
                      K_eff: full causal S/2; blocked-local ~1.5w;
                      routing k clusters x w^2/S ~= S/k_clusters (+ n*k
                      assignment matmul); decode: K_eff = cache length
                      (full) / 2w (local) / cap (routing pages).
  ssd FLOPs           fwd ~= 2*B*S*(3*d_in*N_state) + intra-chunk
                      2*B*S*Q*H*P  (mamba2 dual form).
  moe dispatch        einsum dispatch+combine: 2 * 2*B*S*E_local_capacity*d.
  HBM bytes/chip      params traffic (x3 train passes, x1 inference; FSDP
                      gathers still land+read in HBM so full-model bytes),
                      optimizer moment r/w, activation r/w with remat,
                      logits, decode KV-cache read (the decode bottleneck).

MFU-style score: est_step = max(terms); train/prefill report
mfu = (6NT ideal)/est_step; decode reports bandwidth fraction
memory_s/est_step (decode is bandwidth-bound by definition).
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
RESULTS = os.path.join(os.path.dirname(__file__), "dryrun_results.json")
OUT = os.path.join(os.path.dirname(__file__), "roofline.json")
CHIPS = {"pod": 256, "multipod": 512}


def _cfg_cell(arch, cell_name, variant):
    from repro.configs import cell_by_name, get_config, routing_for_seq, \
        with_routing
    cfg = get_config(arch)
    cell = cell_by_name(cell_name)
    if variant == "routing":
        cfg = routing_for_seq(with_routing(cfg), cell.seq_len)
    return cfg, cell


def _attn_layers(cfg) -> int:
    from repro.models.transformer import per_layer_specs
    return sum(1 for s in per_layer_specs(cfg)
               if s.kind in ("attn", "moe", "cross"))


def _k_eff(cfg, cell, mode: str) -> float:
    S = cell.seq_len
    if cell.kind == "decode":
        if mode == "full":
            return S
        if mode == "local":
            return 2 * cfg.attn_window
        kc = cfg.routing.num_clusters
        return cfg.routing.window or max(1, S // kc)
    if mode == "full":
        return S / 2
    if mode == "local":
        w = cfg.attn_window if cfg.family == "hybrid" \
            else cfg.routing.local_window
        return 1.5 * w
    kc = cfg.routing.num_clusters
    w = cfg.routing.window or max(1, S // kc)
    return (kc * w * w) / S / 2          # balanced clusters, causal half


def analytic_flops(arch: str, cell_name: str, variant: str) -> Dict:
    cfg, cell = _cfg_cell(arch, cell_name, variant)
    B, S = cell.global_batch, cell.seq_len
    toks = B * (1 if cell.kind == "decode" else S)
    dh, H = cfg.head_dim_, cfg.num_heads
    n_attn = _attn_layers(cfg)
    N_act = cfg.active_param_count()
    # ---- matmul term (params touched per token)
    mat = 2.0 * N_act * toks
    # ---- attention term
    attn = 0.0
    if cfg.family != "ssm":
        if cfg.attention == "local+routing":
            from repro.models.transformer import head_split
            Hl, Hr, _, _ = head_split(cfg)
            attn = 4.0 * toks * dh * (
                Hl * _k_eff(cfg, cell, "local")
                + Hr * _k_eff(cfg, cell, "routing")) * n_attn
            # routing assignment: n x k matmul per routing layer
            attn += 2.0 * toks * dh * cfg.routing.num_clusters * Hr * n_attn
        else:
            mode = {"full": "full", "local": "local",
                    "routing": "routing"}.get(cfg.attention, "full")
            attn = 4.0 * toks * dh * H * _k_eff(cfg, cell, mode) * n_attn
    # ---- ssd term
    ssd = 0.0
    if cfg.family == "ssm":
        from repro.models.ssm import ssm_spec
        s = ssm_spec(cfg)
        q = 1 if cell.kind == "decode" else min(s.chunk, S)
        ssd = (2.0 * toks * 3 * s.d_inner * s.nstate
               + 2.0 * toks * q * s.nheads * s.headdim) * cfg.num_layers
    # ---- moe dispatch term (einsum dispatch/combine)
    moe = 0.0
    if cfg.family == "moe":
        E = cfg.moe_experts
        C = max(1, int(cfg.moe_capacity_factor
                       * (1 if cell.kind == "decode" else S) / E))
        n_moe = len([i for i in range(cfg.num_layers)
                     if i % cfg.moe_interleave == 0])
        # dispatch + combine einsums: (B,N,E,C) x (B,N,d) each
        moe = 2 * 2.0 * B * (1 if cell.kind == "decode" else S) \
            * E * C * cfg.d_model * n_moe
    fwd = mat + attn + ssd + moe
    mult = 3.0 if cell.kind == "train" else 1.0
    remat_extra = fwd if cell.kind == "train" else 0.0
    total = fwd * mult + remat_extra
    useful = (6.0 if cell.kind == "train" else 2.0) * N_act * toks
    return {"total": total, "useful": useful, "fwd": fwd}


def analytic_bytes_per_chip(arch: str, cell_name: str, variant: str,
                            chips: int) -> float:
    cfg, cell = _cfg_cell(arch, cell_name, variant)
    B, S = cell.global_batch, cell.seq_len
    N = cfg.param_count()
    pbytes = 2.0                     # bf16 params
    d = cfg.d_model
    L = cfg.num_layers
    if cell.kind == "train":
        toks_local = B * S / chips
        # params: fwd + bwd + remat reads + grad write (model is spread over
        # at most `chips`; FSDP gathers still land in HBM and get read)
        model_io = 4.0 * N * pbytes / min(chips, 256)
        opt_io = 2.0 * N * (4.0 if cfg.param_count() < 20e9 else 0.5) / chips
        act_io = toks_local * d * 2.0 * L * 4.0      # save+read, remat pass
        logits = toks_local * cfg.vocab_size * 4.0 / 16 * 2
        return model_io + opt_io + act_io + logits
    if cell.kind == "prefill":
        toks_local = B * S / chips
        return N * pbytes / min(chips, 16) + toks_local * d * 2.0 * L * 2.0
    # decode: read the whole local model shard + local cache once
    model_local = N * pbytes / 16                    # TP-sharded params
    if cfg.param_count() > 20e9:
        model_local = N * pbytes / chips             # FSDP-sharded
    cache_local = _cache_bytes(cfg, cell) / chips
    return model_local + cache_local


def _cache_bytes(cfg, cell) -> float:
    B, S = cell.global_batch, cell.seq_len
    dh = cfg.head_dim_
    if cfg.family == "ssm":
        from repro.models.ssm import ssm_spec
        s = ssm_spec(cfg)
        return B * s.nheads * s.nstate * s.headdim * 4.0 * cfg.num_layers
    n_attn = _attn_layers(cfg)
    if cfg.attention == "full":
        return 2.0 * B * cfg.num_kv_heads * S * dh * 2.0 * n_attn
    if cfg.attention == "local":
        return 2.0 * B * cfg.num_kv_heads * 2 * cfg.attn_window * dh * 2.0 \
            * n_attn
    # local+routing: ring + the one page each query reads
    from repro.models.transformer import head_split
    Hl, Hr, kvl, kvr = head_split(cfg)
    kc = cfg.routing.num_clusters
    cap = cfg.routing.window or max(1, S // kc)
    ring = 2.0 * B * kvl * 2 * cfg.routing.local_window * dh * 2.0
    page = 2.0 * B * Hr * cap * dh * 2.0
    return (ring + page) * n_attn


def roofline_row(key: str, rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    arch, cell, mesh, variant = key.split("|")
    chips = CHIPS[mesh]
    fl = analytic_flops(arch, cell, variant)
    t_c = fl["total"] / (chips * PEAK_FLOPS)
    t_m = analytic_bytes_per_chip(arch, cell, variant, chips) / HBM_BW
    w = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
         "all-to-all": 1.0, "collective-permute": 1.0}
    coll = rec["collectives"]
    t_x = sum(coll[k]["bytes"] * w[k] for k in w) / ICI_BW
    est = max(t_c, t_m, t_x)
    dom = {t_c: "compute", t_m: "memory", t_x: "collective"}[est]
    ideal = fl["useful"] / (chips * PEAK_FLOPS)
    kind = "decode" if cell.startswith(("decode", "long")) else "train"
    score = (t_m / est) if kind == "decode" else (ideal / est)
    return {
        "arch": arch, "cell": cell, "mesh": mesh, "variant": variant,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "est_step_s": est, "dominant": dom,
        "model_flops": fl["useful"], "analytic_flops": fl["total"],
        "hlo_flops_per_dev": rec["flops_per_device"],
        "useful_ratio": fl["useful"] / fl["total"],
        "score": score, "score_kind": "bw_frac" if kind == "decode"
        else "mfu",
        "peak_gib": rec["peak_device_bytes"] / 2 ** 30,
        "fits_16g": rec["peak_device_bytes"] < 16 * 2 ** 30,
        "coll_raw_gib": coll.get("raw_total_bytes", 0) / 2 ** 30,
        "coll_gib": coll["total_bytes"] / 2 ** 30,
    }


def build(results_path: str = RESULTS) -> Dict[str, Dict]:
    with open(results_path) as f:
        res = json.load(f)
    rows = {}
    for key, rec in sorted(res.items()):
        row = roofline_row(key, rec)
        if row:
            rows[key] = row
    return rows


def markdown_table(rows: Dict[str, Dict], mesh: str = "pod") -> str:
    hdr = ("| arch | cell | var | compute s | memory s | coll s | dom | "
           "6ND/analytic | score | peak GiB | fits |\n"
           "|---|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for key, r in rows.items():
        if r["mesh"] != mesh:
            continue
        lines.append(
            f"| {r['arch']} | {r['cell']} | {r['variant'][:4]} "
            f"| {r['compute_s']:.2e} | {r['memory_s']:.2e} "
            f"| {r['collective_s']:.2e} | {r['dominant'][:4]} "
            f"| {r['useful_ratio']:.2f} "
            f"| {r['score']:.2f} ({r['score_kind']}) "
            f"| {r['peak_gib']:.1f} | {'y' if r['fits_16g'] else 'N'} |")
    return hdr + "\n".join(lines)


# ---------------------------------------------------------------------------
# Attention-op roofline: routing (paged fused) vs flash, the O(n^1.5)
# crossover (§Roofline, op level — feeds the routing sweep's record)
# ---------------------------------------------------------------------------
ATTN_SEQ_LENS = (1024, 4096, 8192, 16384, 32768)


def _attn_terms(N: int, B: int, H: int, dh: int, impl: str,
                dtype_bytes: float = 2.0) -> Dict:
    """Analytic FLOPs + HBM bytes for one attention op at sequence N.

    ``flash``    full causal: every query scores N/2 keys -> O(n^2) FLOPs;
                 q/k/v/o planes streamed once -> 4*N*dh bytes/head.
    ``routing``  paged fused kernel, paper scaling kc = w = sqrt(N):
                 each query scores w/2 in-cluster keys (causal half) plus
                 the n x kc assignment matmul -> O(n^1.5) FLOPs. The pager
                 streams each sequence row into VMEM exactly once per
                 membership (per-row DMA), so bytes stay the same four
                 planes as flash + 4-byte membership indices — no
                 N-resident VMEM term and no gathered copies.
    ``gathered`` same FLOPs as routing, but the XLA gather materializes
                 (B,H,kc,w,dh) copies of q/k/v in HBM: one extra write +
                 one extra read of three planes (and the output scatter),
                 ~3x the plane traffic the fused kernel pays.
    """
    w = max(1.0, math.sqrt(N))
    kc = N / w
    plane = B * H * N * dh * dtype_bytes
    if impl == "flash":
        flops = 4.0 * B * H * N * (N / 2.0) * dh
        bytes_ = 4.0 * plane
    else:
        flops = (4.0 * B * H * N * (w / 2.0) * dh        # in-cluster scores
                 + 2.0 * B * H * N * kc * dh)            # assignment matmul
        bytes_ = 4.0 * plane + B * H * N * 4.0           # + int32 members
        if impl == "gathered":
            bytes_ += 2.0 * 3.0 * plane + plane          # copy w+r, scatter
    t_c, t_m = flops / PEAK_FLOPS, bytes_ / HBM_BW
    return {"flops": flops, "hbm_bytes": bytes_,
            "compute_s": t_c, "memory_s": t_m,
            "est_s": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def attention_roofline(B: int = 1, H: int = 8, dh: int = 128,
                       seq_lens=ATTN_SEQ_LENS,
                       dtype_bytes: float = 2.0) -> Dict:
    """Routing-vs-flash roofline across N + the predicted crossover: the
    smallest N where the routing op's est time beats flash on a v5e.
    Below it both ops sit on the same memory roof (identical plane
    traffic) and flash's simpler schedule wins in practice; past it
    flash goes compute-bound on its O(n^2) term while routing stays on
    the O(n^1.5) curve — est_s ratios grow ~sqrt(N) from there."""
    points = []
    for N in seq_lens:
        row = {"N": N}
        for impl in ("flash", "routing", "gathered"):
            row[impl] = _attn_terms(N, B, H, dh, impl, dtype_bytes)
        row["routing_speedup_vs_flash"] = round(
            row["flash"]["est_s"] / row["routing"]["est_s"], 3)
        row["paged_vs_gathered_bytes"] = round(
            row["gathered"]["hbm_bytes"] / row["routing"]["hbm_bytes"], 3)
        points.append(row)
    crossover = None
    for N in range(256, max(seq_lens) + 1, 256):
        if (_attn_terms(N, B, H, dh, "routing", dtype_bytes)["est_s"]
                < _attn_terms(N, B, H, dh, "flash", dtype_bytes)["est_s"]):
            crossover = N
            break
    return {"arch": "tpu_v5e",
            "peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
            "shape": {"B": B, "H": H, "dh": dh,
                      "dtype_bytes": dtype_bytes,
                      "window": "sqrt(N)"},
            "predicted_crossover_n": crossover,
            "points": points}


def attention_markdown(rec: Dict) -> str:
    hdr = ("| N | flash est s | routing est s | flash bound | "
           "routing bound | routing speedup | gathered/paged bytes |\n"
           "|---|---|---|---|---|---|---|\n")
    lines = []
    for p in rec["points"]:
        lines.append(
            f"| {p['N']} | {p['flash']['est_s']:.2e} "
            f"| {p['routing']['est_s']:.2e} "
            f"| {p['flash']['bound'][:4]} | {p['routing']['bound'][:4]} "
            f"| {p['routing_speedup_vs_flash']:.2f}x "
            f"| {p['paged_vs_gathered_bytes']:.2f}x |")
    return hdr + "\n".join(lines)


def main():
    import sys
    if "--attention" in sys.argv[1:]:
        rec = attention_roofline()
        print(f"attention roofline (v5e, w = sqrt(N)); predicted "
              f"routing-beats-flash crossover at N = "
              f"{rec['predicted_crossover_n']}")
        print(attention_markdown(rec))
        return
    rows = build()
    with open(OUT, "w") as f:
        json.dump(rows, f, indent=1)
    for mesh in ("pod", "multipod"):
        print(f"\n===== mesh: {mesh} ({CHIPS[mesh]} chips) =====")
        print(markdown_table(rows, mesh))
    pod = [r for r in rows.values() if r["mesh"] == "pod"]
    print("\nworst scores (pod):")
    for r in sorted(pod, key=lambda r: r["score"])[:5]:
        print(f"  {r['arch']}|{r['cell']}|{r['variant']}: "
              f"{r['score']:.3f} ({r['score_kind']}) dom={r['dominant']}")
    print("most collective-bound (pod):")
    for r in sorted(pod, key=lambda r: -(r["collective_s"]
                                         / max(r["est_step_s"], 1e-12)))[:5]:
        print(f"  {r['arch']}|{r['cell']}|{r['variant']}: "
              f"coll={r['collective_s']:.2e}s of est {r['est_step_s']:.2e}s")


if __name__ == "__main__":
    main()
