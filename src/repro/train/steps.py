"""Train/serve step factories: jitted, sharded, donate-friendly.

These bundles are the single source of truth for every entry point —
the real trainer, the co-location stepper, and the multi-pod dry-run all
call ``make_train_bundle`` / ``make_serve_bundle`` so the lowered HLO is
identical across them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec, input_specs
from repro.models import params as pu
from repro.models.factory import build_model
from repro.optim.adamw import (
    OptimizerConfig,
    clip_by_global_norm,
    make_optimizer,
)
from repro.optim.schedules import cosine_with_warmup


def _batch_spec(batch_axes: Tuple[str, ...]):
    return batch_axes if len(batch_axes) > 1 else batch_axes[0]


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


@dataclasses.dataclass
class TrainBundle:
    cfg: ArchConfig
    model: Any
    optimizer: Any
    step_fn: Callable  # jitted (params, opt_state, batch) -> (params, opt_state, metrics)
    abstract_params: Any
    abstract_opt: Any
    param_shardings: Any
    opt_shardings: Any
    batch_shardings: Dict[str, Any]

    def init_state(self, seed: int = 0):
        # create each shard where it lives: built whole, the fp32 optimizer
        # state of a model that needs a mesh would not fit on one device
        key = jax.random.PRNGKey(seed)
        params = jax.jit(self.model.init, out_shardings=self.param_shardings)(key)
        opt_state = jax.jit(self.optimizer.init, out_shardings=self.opt_shardings)(params)
        return params, opt_state


def make_train_bundle(
    cfg: ArchConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Tuple[str, ...] = ("data",),
    opt_cfg: Optional[OptimizerConfig] = None,
    lr_schedule: Optional[Callable] = None,
    grad_clip: float = 1.0,
    q_chunk: int = 1024,
    microbatches: int = 1,
    layout: str = "megatron",  # "megatron" (TP over model axis) | "zero3"
    zero2_grads: bool = False,  # data-shard the fp32 grad accumulator (§Perf)
) -> TrainBundle:
    if layout == "zero3" and mesh is not None:
        # pure-DP ZeRO-3: batch over EVERY mesh axis; weights fully sharded
        # across all chips and gathered per scanned layer (§Perf)
        batch_axes = tuple(mesh.axis_names)
    model = build_model(cfg, mesh, batch_axes, q_chunk=q_chunk)
    opt_cfg = opt_cfg or OptimizerConfig(name=cfg.optimizer)
    optimizer = make_optimizer(opt_cfg)
    lr_schedule = lr_schedule or cosine_with_warmup(3e-4, 100, 10_000)

    defs = model.param_defs()
    if layout == "zero3" and mesh is not None:
        defs_for_specs = pu.strip_model_axis(defs)
        n_all = mesh.size
        param_specs = pu.fsdp_param_specs(defs_for_specs, batch_axes, n_all)
    elif cfg.fsdp and mesh is not None:
        defs_for_specs = defs
        n_data = 1
        for a in batch_axes:
            n_data *= mesh.shape[a]
        param_specs = pu.fsdp_param_specs(defs, batch_axes, n_data)
    else:
        defs_for_specs = defs
        param_specs = pu.partition_specs(defs)
    abstract_params = pu.abstract_params(defs)
    if zero2_grads and mesh is not None:
        _n_data = 1
        for a in batch_axes:
            _n_data *= mesh.shape[a]
        _grad_acc_shardings = jax.tree.map(
            lambda s: _ns(mesh, s), pu.zero_specs(defs_for_specs, batch_axes, _n_data)
        )
    else:
        _grad_acc_shardings = None

    def loss_of(params, batch):
        if cfg.enc_dec:
            return model.loss(
                params, batch["tokens"], batch["labels"], batch["frontend_embeds"]
            )
        kw = {}
        if "frontend_embeds" in batch:
            kw["frontend_embeds"] = batch["frontend_embeds"]
        return model.loss(params, batch["tokens"], batch["labels"], **kw)

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            # gradient accumulation: scan over microbatch slices of the
            # global batch; live activations shrink by the microbatch factor
            # while the lowered collective schedule stays per-microbatch
            # (compute/comm overlap across the accumulation loop).
            def slice_mb(a):
                b = a.shape[0]
                return a.reshape((microbatches, b // microbatches) + a.shape[1:])

            mbs = {k: slice_mb(v) for k, v in batch.items() if hasattr(v, "shape") and v.ndim}

            def shard_acc(t):
                # ZeRO-2: the fp32 accumulator is data-sharded (XLA lowers
                # the per-microbatch reduction as a reduce-scatter); the
                # optimizer consumes it against the equally-sharded m/v.
                if _grad_acc_shardings is None:
                    return t
                return jax.tree.map(
                    jax.lax.with_sharding_constraint, t, _grad_acc_shardings
                )

            def body(acc, mb):
                g_acc, loss_acc, metrics_acc = acc
                (loss, metrics), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mb
                )
                g_acc = shard_acc(
                    jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                )
                metrics_acc = jax.tree.map(lambda a, b: a + b, metrics_acc, metrics)
                return (g_acc, loss_acc + loss, metrics_acc), None

            g0 = shard_acc(
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            )
            mkeys = ["ce", "aux"] + (["mtp_ce"] if cfg.mtp_depth else [])
            m0 = {k: jnp.zeros((), jnp.float32) for k in mkeys}
            from repro.models import flags as _flags

            (grads, loss, metrics), _ = _flags.scan(
                body, (g0, jnp.zeros(()), m0), mbs
            )
            scale = 1.0 / microbatches
            grads = jax.tree.map(lambda g: g * scale, grads)
            loss = loss * scale
            metrics = jax.tree.map(lambda m: m * scale, metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch
            )
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            lr = lr_schedule(opt_state.step)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
        out_metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": gnorm,
            "lr": lr,
            **{k: v.astype(jnp.float32) for k, v in metrics.items()},
        }
        return params, opt_state, out_metrics

    if mesh is None:
        step_fn = jax.jit(train_step, donate_argnums=(0, 1))
        return TrainBundle(
            cfg, model, optimizer, step_fn, abstract_params, None, None, None, {}
        )

    bspec = _batch_spec(batch_axes)
    param_sh = jax.tree.map(lambda s: _ns(mesh, s), param_specs)
    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]
    zspecs = pu.zero_specs(defs_for_specs, batch_axes, n_data)
    opt_specs = optimizer.state_specs(param_specs, zspecs)
    opt_sh = jax.tree.map(lambda s: _ns(mesh, s), opt_specs)
    batch_sh = {
        "tokens": _ns(mesh, P(bspec, None)),
        "labels": _ns(mesh, P(bspec, None)),
        "frontend_embeds": _ns(mesh, P(bspec, None, None)),
    }
    abstract_opt = jax.eval_shape(optimizer.init, abstract_params)

    def batch_shardings_for(batch_keys):
        return {k: batch_sh[k] for k in batch_keys}

    step_fn = jax.jit(
        train_step,
        donate_argnums=(0, 1),
        in_shardings=(param_sh, opt_sh, None),  # batch sharding via device_put
        out_shardings=(param_sh, opt_sh, None),
    )
    return TrainBundle(
        cfg,
        model,
        optimizer,
        step_fn,
        abstract_params,
        abstract_opt,
        param_sh,
        opt_sh,
        batch_sh,
    )


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeBundle:
    cfg: ArchConfig
    model: Any
    prefill_fn: Callable  # (params, tokens[, frontend]) -> (logits, cache)
    decode_fn: Callable  # (params, cache, tokens, cache_len) -> (logits, cache)
    abstract_params: Any
    param_shardings: Any
    cache_shardings: Any
    abstract_cache: Any


def make_serve_bundle(
    cfg: ArchConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Tuple[str, ...] = ("data",),
    batch: int = 1,
    max_len: int = 2048,
    q_chunk: int = 1024,
) -> ServeBundle:
    model = build_model(cfg, mesh, batch_axes, q_chunk=q_chunk)
    defs = model.param_defs()
    abstract_params = pu.abstract_params(defs)

    def prefill(params, tokens, frontend_embeds=None):
        if cfg.enc_dec:
            return model.prefill(params, tokens, frontend_embeds, max_len=max_len)
        return model.prefill(
            params, tokens, frontend_embeds=frontend_embeds, max_len=max_len
        )

    decode = model.decode_step

    abstract_cache = jax.eval_shape(lambda: model.make_cache(batch, max_len))

    if mesh is None:
        return ServeBundle(
            cfg,
            model,
            jax.jit(prefill),
            jax.jit(decode, donate_argnums=(1,)),
            abstract_params,
            None,
            None,
            abstract_cache,
        )

    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]
    if cfg.fsdp:
        p_specs = pu.fsdp_param_specs(defs, batch_axes, n_data)
    else:
        p_specs = pu.partition_specs(defs)
    param_sh = jax.tree.map(lambda s: _ns(mesh, s), p_specs)
    cache_specs = model.cache_specs()
    if batch % n_data:
        # batch (e.g. long_500k B=1) cannot shard over the data axes: the
        # cache stays seq-sharded only.
        def _strip(spec: P) -> P:
            entries = []
            for e in tuple(spec):
                es = e if isinstance(e, tuple) else (e,)
                if any(a in batch_axes for a in es if a):
                    entries.append(None)
                else:
                    entries.append(e)
            return P(*entries)

        cache_specs = jax.tree.map(
            _strip, cache_specs, is_leaf=lambda v: isinstance(v, P)
        )
    cache_sh = jax.tree.map(
        lambda s: _ns(mesh, s), cache_specs, is_leaf=lambda v: isinstance(v, P)
    )
    # attach shardings to the abstract params (prefill has an optional
    # trailing arg, so in_shardings cannot be a fixed-arity tuple there)
    abstract_params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_params,
        param_sh,
    )
    prefill_fn = jax.jit(
        prefill,
        out_shardings=(None, cache_sh),
    )
    decode_fn = jax.jit(
        decode,
        donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh, None, None),
        out_shardings=(None, cache_sh),
    )
    return ServeBundle(
        cfg, model, prefill_fn, decode_fn, abstract_params, param_sh, cache_sh,
        abstract_cache,
    )
