"""Training step: loss, grads, microbatch accumulation, optimizer update.

`make_train_step(run)` returns a pure `(TrainState, batch) -> (TrainState,
metrics)` suitable for jax.jit / pjit. The k-means routing state rides in
TrainState and is refreshed from the forward pass (functional EMA).
Gradient accumulation scans over microbatches (bounds activation memory on
the train_4k cells); remat policy applies inside the model stack.

With `TrainConfig.grad_compression == "int8_ef"` the returned step is the
`shard_map`-based data-parallel variant (`make_compressed_train_step`):
every device computes grads on its shard of the batch, the cross-device
gradient mean goes over the wire as int8 with an error-feedback residual
carried in `TrainState.ef_state`, and the optimizer update runs replicated.
DESIGN.md §6 documents the wire format and residual placement.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.models.model import apply_model, lm_loss, next_token_batch
from repro.obs import routing_stats as obs_rt
from repro.obs.trace import span
from repro.optim import make_optimizer, make_schedule

MOE_LB_COEF = 1e-2
MOE_Z_COEF = 1e-3


class TrainState(NamedTuple):
    params: Any
    kstate: Any
    opt_state: Any
    step: jax.Array
    # fp32 error-feedback residuals for int8 gradient compression: a
    # param-shaped tree whose leaves carry a leading (D,) device axis
    # (device i's residual is leaf[i]; sharded over the data axes by
    # dist/sharding.ef_sharding). None when grad_compression == "none".
    ef_state: Any = None


def _ef_devices(mesh=None) -> int:
    if mesh is not None:
        from repro.dist.sharding import _axis_size, dp_axes
        return _axis_size(mesh, dp_axes(mesh))
    return len(jax.devices())


def init_ef_state(params, num_devices: int):
    """Zero residuals, (D, *param.shape) fp32 per leaf. The tree is D x
    total-params fp32: initialize it under ``jax.jit(...,
    out_shardings=...)`` (as Trainer does on a mesh) so each device
    builds only its own residual."""
    return jax.tree.map(
        lambda p: jnp.zeros((num_devices,) + tuple(p.shape), jnp.float32),
        params)


def init_train_state(run: RunConfig, key: jax.Array,
                     mesh=None) -> TrainState:
    """``mesh`` sizes the error-feedback residual's device axis when
    grad compression is on (default: all local devices)."""
    from repro.models.model import init_model
    params, kstate = init_model(run.model, key)
    opt_init, _ = make_optimizer(run.train)
    ef = (init_ef_state(params, _ef_devices(mesh))
          if run.train.grad_compression == "int8_ef" else None)
    return TrainState(params, kstate, opt_init(params),
                      jnp.zeros((), jnp.int32), ef)


def make_loss_fn(run: RunConfig, impl=None, moe_impl="einsum",
                 constrain_fn: Optional[Callable] = None, mesh=None):
    mc, tc = run.model, run.train

    def loss_fn(params, kstate, batch, drop_rng):
        if mc.family == "encoder":
            inputs, targets = batch, batch["tokens"]
            loss_mask = batch.get("mask_spans")
        else:
            inputs, targets = next_token_batch(batch)
            loss_mask = None
        # needs_grad: this forward is differentiated — attention backend
        # resolution excludes (or, forced, loudly refuses) non-VJP kernels
        logits, new_k, aux = apply_model(
            params, kstate, inputs, mc, update_state=True, impl=impl,
            moe_impl=moe_impl, remat=tc.remat, drop_rng=drop_rng,
            constrain_fn=constrain_fn, mesh=mesh, needs_grad=True)
        pad = inputs.get("pad_mask")
        with span("model/loss"):
            loss, metrics = lm_loss(logits, targets, pad, tc.z_loss,
                                    loss_mask)
        if mc.family == "moe":
            loss = (loss + MOE_LB_COEF * aux["moe_lb_loss"]
                    + MOE_Z_COEF * aux["moe_z_loss"])
        metrics = dict(metrics)
        aux = dict(aux)
        rstats = aux.pop("routing_stats", None)
        metrics.update({k: v for k, v in aux.items()})
        if rstats is not None:
            # routing-health telemetry (RoutingConfig.stats): model-wide
            # scalars ("routing/entropy", ...) + per-layer detail arrays
            # ("rt/{seg}/{layer}/{field}", leading (G,) group axis)
            metrics.update(obs_rt.summarize(rstats))
            metrics.update(obs_rt.flatten(rstats))
        metrics["loss"] = loss
        return loss, (new_k, metrics)

    return loss_fn


def global_norm(tree) -> jax.Array:
    sq = jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))),
                      tree)
    return jnp.sqrt(jax.tree_util.tree_reduce(jnp.add, sq, 0.0))


def clip_by_global_norm(grads, max_norm):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), gn


def make_grad_fn(run: RunConfig, loss_fn,
                 grad_constrain: Optional[Callable] = None):
    """`(params, kstate, batch, drop_rng) -> (grads, new_kstate, metrics)`
    with microbatch accumulation per `TrainConfig.grad_accum`. Shared by
    the plain (GSPMD) and the shard_map/compressed train-step variants —
    inside shard_map it operates on the device-local batch shard."""
    tc = run.train
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    gc = grad_constrain or (lambda g: g)

    def grad_fn(params, kstate, batch, drop_rng):
        A = tc.grad_accum
        if A <= 1:
            (loss, (new_k, metrics)), grads = vg(params, kstate, batch,
                                                 drop_rng)
            return gc(grads), new_k, dict(metrics)

        def micro(b):
            return jax.tree.map(
                lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:]), b)

        mb = micro(batch)
        acc_dt = jnp.dtype(tc.accum_dtype)

        def body(carry, xs):
            grads_acc, kst = carry
            (loss, (nk, metrics)), g = vg(params, kst, xs, drop_rng)
            grads_acc = gc(jax.tree.map(
                lambda a, b: a + b.astype(acc_dt), grads_acc, g))
            # metrics leave as stacked ys (meaned below) rather than a
            # carry: the metric *structure* is dynamic (routing-health
            # arrays appear per layer when RoutingConfig.stats is on),
            # so there is no fixed zero-template to initialize a carry
            return (grads_acc, nk), (loss, metrics)

        zeros = gc(jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                                params))
        (gacc, new_k), (losses, mstack) = jax.lax.scan(
            body, (zeros, kstate), mb)
        grads = jax.tree.map(lambda g: (g / A).astype(jnp.float32)
                             if g.dtype == jnp.float32 else g / A, gacc)
        metrics = {k: v.mean(0) for k, v in mstack.items()}
        metrics["loss"] = losses.mean()
        return grads, new_k, metrics

    return grad_fn


def _finish_step(tc, schedule, opt_update, ts: TrainState, grads, new_k,
                 metrics, new_ef):
    """Shared tail: clip, lr, optimizer update, state assembly."""
    with span("train/optimizer"):
        grads, gn = clip_by_global_norm(grads, tc.grad_clip)
        lr = schedule(ts.step + 1)
        new_params, new_opt = opt_update(grads, ts.opt_state, ts.params, lr)
    metrics["grad_norm"] = gn
    metrics["lr"] = lr
    return (TrainState(new_params, new_k, new_opt, ts.step + 1, new_ef),
            metrics)


def _drop_rng(run: RunConfig, step):
    return (jax.random.fold_in(jax.random.PRNGKey(run.train.seed), step)
            if run.model.dropout > 0 else None)


def make_train_step(run: RunConfig, impl=None, moe_impl="einsum",
                    constrain_fn: Optional[Callable] = None,
                    grad_transform: Optional[Callable] = None,
                    grad_constrain: Optional[Callable] = None,
                    mesh=None):
    """grad_transform: optional hook (e.g. gradient compression) applied to
    the accumulated grads before clipping. grad_constrain: sharding
    constraint pinning the fp32 accumulation buffers to the param layout
    (without it GSPMD may replicate the scan carry — 13x memory on the
    400B config, see EXPERIMENTS.md §Perf). mesh: the data mesh for the
    compressed variant (grad_compression == "int8_ef" dispatches to
    `make_compressed_train_step`; the GSPMD-only hooks are incompatible
    with the shard_map path and raise rather than silently dropping)."""
    if run.train.grad_compression == "int8_ef":
        dropped = [n for n, v in (("constrain_fn", constrain_fn),
                                  ("grad_transform", grad_transform),
                                  ("grad_constrain", grad_constrain))
                   if v is not None]
        if dropped:
            raise ValueError(
                f"{dropped} have no effect inside the shard_map-based "
                "int8_ef train step (no GSPMD partitioning to constrain); "
                "pass None or use grad_compression='none'")
        return make_compressed_train_step(run, impl=impl, moe_impl=moe_impl,
                                          mesh=mesh)
    tc = run.train
    # the mesh reaches attention-backend resolution (repro.attn): a
    # >1-device GSPMD mesh excludes supports_mesh=False kernels. The
    # shard_map/compressed variant stays mesh-less on purpose — inside
    # shard_map every program is single-device.
    loss_fn = make_loss_fn(run, impl, moe_impl, constrain_fn, mesh=mesh)
    _, opt_update = make_optimizer(tc)
    schedule = make_schedule(tc, run.model.d_model)
    grad_fn = make_grad_fn(run, loss_fn, grad_constrain)

    def train_step(ts: TrainState, batch: Dict[str, jax.Array]):
        with span("train/grad"):
            grads, new_k, metrics = grad_fn(ts.params, ts.kstate, batch,
                                            _drop_rng(run, ts.step))
        if grad_transform is not None:
            grads = grad_transform(grads)
        return _finish_step(tc, schedule, opt_update, ts, grads, new_k,
                            metrics, ts.ef_state)

    return train_step


def make_compressed_train_step(run: RunConfig, impl=None,
                               moe_impl="einsum", mesh=None):
    """Data-parallel train step with int8 error-feedback gradient
    compression (DESIGN.md §6).

    The grad computation runs inside `shard_map` over the data axes:
    params/kstate replicated, batch sharded on its leading dim, each
    device differentiating its local shard. The cross-device gradient
    mean then goes through `dist/compression.int8_ef_psum_mean` — int8
    payloads on the wire, per-device fp32 residual threaded through
    `TrainState.ef_state` — and kstate/metrics are pmean-synced (fp32,
    tiny). The optimizer update runs on the replicated mean outside the
    shard_map, so devices stay bit-identical.

    Data-parallel only: a mesh with a >1 "model" axis is rejected (the
    compressed exchange flattens whole gradients; tensor-parallel layouts
    go through the GSPMD path). Dropout uses one shared rng per step
    across devices. On a 1-device mesh the wire vanishes and the step
    degenerates to the exact uncompressed computation.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.compression import int8_ef_psum_mean
    from repro.dist.sharding import _axis_size, dp_axes

    tc = run.train
    if mesh is None:
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((len(jax.devices()),), ("data",))
    if _axis_size(mesh, "model") > 1:
        raise ValueError(
            "int8_ef grad compression is data-parallel only; got a mesh "
            f"with model axis size {_axis_size(mesh, 'model')}")
    dp = dp_axes(mesh)
    D = _axis_size(mesh, dp)
    if tc.global_batch % max(D, 1):
        raise ValueError(f"global_batch={tc.global_batch} must divide over "
                         f"{D} data-parallel devices")
    loss_fn = make_loss_fn(run, impl, moe_impl, None)
    grad_fn = make_grad_fn(run, loss_fn)
    _, opt_update = make_optimizer(tc)
    schedule = make_schedule(tc, run.model.d_model)

    def pmean_tree(t):
        return jax.tree.map(
            lambda a: jax.lax.pmean(a, dp)
            if jnp.issubdtype(a.dtype, jnp.inexact) else a, t)

    def sync_metrics(metrics):
        # means of per-shard means are exact for equal shard sizes —
        # except count-like entries, which are sums over the shard
        return {k: (jax.lax.psum(v, dp) if k == "tokens"
                    else jax.lax.pmean(v, dp))
                for k, v in metrics.items()}

    # leaves too small to amortize the int8 machinery (norm scales,
    # biases: padding to D*group would exceed the payload saved) take
    # the exact fp32 pmean; their residual stays identically zero
    min_compress = D * 128

    def sharded_grads(params, kstate, ef, batch, drop_rng):
        with span("train/grad"):
            grads, new_k, metrics = grad_fn(params, kstate, batch,
                                            drop_rng)
        gl, tdef = jax.tree_util.tree_flatten(grads)
        el = jax.tree_util.tree_leaves(ef)
        with span("train/exchange"):
            pairs = [int8_ef_psum_mean(g, e[0], dp)
                     if g.size >= min_compress
                     else (jax.lax.pmean(g, dp), e[0])
                     for g, e in zip(gl, el)]
        mean_g = jax.tree_util.tree_unflatten(tdef, [m for m, _ in pairs])
        new_ef = jax.tree_util.tree_unflatten(tdef,
                                              [e[None] for _, e in pairs])
        # kstate EMA / metrics are computed on the local shard; sync the
        # fp32 leaves exactly (tiny payloads — not worth compressing)
        return mean_g, new_ef, pmean_tree(new_k), sync_metrics(metrics)

    smapped = jax.shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(P(), P(), P(dp), P(dp), P()),
        out_specs=(P(), P(dp), P(), P()),
        check_vma=False)

    def train_step(ts: TrainState, batch: Dict[str, jax.Array]):
        lead = {e.shape[0] for e in jax.tree_util.tree_leaves(ts.ef_state)}
        if lead and lead != {D}:
            # a mismatched residual would be silently row-sliced by the
            # shard_map in_spec — wrong EF bookkeeping, the exact bias
            # this machinery exists to cancel
            raise ValueError(
                f"ef_state device axis {sorted(lead)} != mesh data size "
                f"{D}; init_train_state(run, key, mesh=) with this mesh")
        mean_g, new_ef, new_k, metrics = smapped(
            ts.params, ts.kstate, ts.ef_state, batch,
            _drop_rng(run, ts.step))
        return _finish_step(tc, schedule, opt_update, ts, mean_g, new_k,
                            metrics, new_ef)

    return train_step
