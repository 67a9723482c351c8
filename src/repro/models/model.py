"""Top-level model: embed -> stack -> final norm -> logits, plus losses.

`init_model` returns (params, kstate); `apply_model` is pure and returns
(logits, new_kstate, aux). The k-means centroid state is functional: the
caller (train step) decides whether to keep the update.

Batch dict keys:
  tokens        (B, S) int32 — LM inputs / hubert codebook targets
  positions     (B, S) int32 (optional, defaults to arange)
  pad_mask      (B, S) bool  (optional)
  features      (B, S, d)    — [audio] stub frontend frame embeddings
  image_embeds  (B, M, d)    — [vlm] stub frontend patch embeddings
  mask_spans    (B, S) bool  — [audio] masked-prediction positions
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.obs.trace import span


def init_model(cfg: ModelConfig, key: jax.Array):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    params: Dict[str, Any] = {
        "embed": L.init_embed(ks[0], cfg.padded_vocab, cfg.d_model, dt,
                              cfg.tie_embeddings),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dt),
    }
    if cfg.family == "encoder":
        params["mask_emb"] = (jax.random.normal(ks[2], (cfg.d_model,))
                              * 0.02).astype(dt)
    seg_params, seg_kstate = T.init_stack(ks[1], cfg)
    params["stack"] = seg_params
    return params, seg_kstate


def apply_model(params, kstate, batch: Dict[str, jax.Array],
                cfg: ModelConfig, *, update_state: bool = True,
                impl: Optional[str] = None, moe_impl: str = "einsum",
                remat: str = "none", drop_rng: Optional[jax.Array] = None,
                constrain_fn=None, mesh=None, needs_grad: bool = False):
    positions = batch.get("positions")
    pad_mask = batch.get("pad_mask")
    if cfg.family == "encoder":
        x = batch["features"].astype(jnp.dtype(cfg.dtype))
        if "mask_spans" in batch:
            x = jnp.where(batch["mask_spans"][..., None],
                          params["mask_emb"].astype(x.dtype), x)
    else:
        with span("model/embed"):
            x = L.embed(params["embed"], batch["tokens"])
    x, new_kstate, aux = T.apply_stack(
        params["stack"], kstate, x, cfg,
        positions=positions, pad_mask=pad_mask,
        image_embeds=batch.get("image_embeds"),
        update_state=update_state, impl=impl, moe_impl=moe_impl,
        remat=remat, drop_rng=drop_rng, constrain_fn=constrain_fn,
        mesh=mesh, needs_grad=needs_grad)
    epilogue = getattr(constrain_fn, "epilogue", None)
    if epilogue is not None:
        x = epilogue(x)          # SP epilogue: re-gather seq for the LM head
    # the head (final norm, unembed) and the loss share one span
    with span("model/loss"):
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.logits_out(params["embed"], x, cfg.tie_embeddings,
                              cfg.logit_softcap)
        logits = mask_vocab_pad(logits, cfg)
    return logits, new_kstate, aux


def mask_vocab_pad(logits, cfg):
    """Padding rows of the (256-aligned) embedding table never win: mask
    their logits so CE/argmax see only the logical vocabulary."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(valid, logits, -1e9)


def lm_loss(logits: jax.Array, targets: jax.Array,
            pad_mask: Optional[jax.Array] = None,
            z_loss: float = 0.0,
            loss_mask: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Token-mean cross entropy in fp32. logits (B,S,V), targets (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    mask = jnp.ones(targets.shape, jnp.float32)
    if pad_mask is not None:
        mask = mask * pad_mask.astype(jnp.float32)
    if loss_mask is not None:
        mask = mask * loss_mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    metrics = {"nll": loss, "tokens": denom}
    if z_loss:
        zl = z_loss * ((lse ** 2) * mask).sum() / denom
        loss = loss + zl
        metrics["z_loss"] = zl
    return loss, metrics


def next_token_batch(batch: Dict[str, jax.Array]) -> Tuple[Dict, jax.Array]:
    """Shift tokens for next-token prediction: inputs[t] predicts tokens[t+1]."""
    toks = batch["tokens"]
    inputs = dict(batch)
    inputs["tokens"] = toks[:, :-1]
    for k in ("positions", "pad_mask", "mask_spans"):
        if k in batch:
            inputs[k] = batch[k][:, :-1]
    if "features" in batch:
        inputs["features"] = batch["features"][:, :-1]
    return inputs, toks[:, 1:]
