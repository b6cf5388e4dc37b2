"""LM train-step factory: loss -> grads -> clip -> AdamW, with mixed
precision (f32 master params, bf16 compute) and optional int8
error-feedback gradient compression on the data-parallel reduction.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.nn.config import ModelConfig
from repro.nn import transformer as T
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      clip_by_global_norm)
from repro.training.schedule import cosine_schedule, wsd_schedule


def cast_params_for_compute(cfg: ModelConfig, params):
    """Cast matrix params to the compute dtype once, *before* the layer
    scan, so FSDP weight all-gathers move bf16 (half the f32 bytes).
    Measured on granite_3_2b/train_4k: every collective in the compiled
    step was f32 because XLA gathers the stored f32 param and converts
    after — see EXPERIMENTS.md SPerf iteration 2.  1-D params (norm
    scales, biases) stay f32: they are tiny and replicated."""
    dt = cfg.compute_dtype
    return jax.tree.map(
        lambda p: p.astype(dt) if (hasattr(p, "ndim") and p.ndim > 1
                                   and p.dtype == jnp.float32) else p,
        params)


def make_loss_fn(cfg: ModelConfig, sc=T.no_sc, q_chunk: int = 512,
                 loss_chunk: int = 256, remat: bool = True,
                 cast_weights: bool = True):
    def loss_fn(params, batch):
        if cast_weights:
            params = cast_params_for_compute(cfg, params)
        return T.forward_train(cfg, params, batch, sc, q_chunk, loss_chunk,
                               remat)
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    sc=T.no_sc, *, peak_lr: float = 3e-4,
                    warmup: int = 2000, total_steps: int = 100_000,
                    q_chunk: int = 512, loss_chunk: int = 256,
                    remat: bool = True,
                    grad_transform: Optional[Callable] = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  `grad_transform` hooks gradient compression."""
    loss_fn = make_loss_fn(cfg, sc, q_chunk, loss_chunk, remat)
    sched = wsd_schedule if cfg.wsd_schedule else cosine_schedule

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        lr = sched(opt_state["count"] + 1, peak_lr=peak_lr, warmup=warmup,
                   total=total_steps)
        params, opt_state = adamw_update(opt_cfg, grads, opt_state, params, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def make_grad_accum_train_step(cfg: ModelConfig,
                               opt_cfg: AdamWConfig = AdamWConfig(),
                               sc=T.no_sc, *, micro_steps: int = 4,
                               peak_lr: float = 3e-4, warmup: int = 2000,
                               total_steps: int = 100_000,
                               q_chunk: int = 512, loss_chunk: int = 256,
                               grad_transform: Optional[Callable] = None):
    """Gradient accumulation over `micro_steps` microbatches via lax.scan
    (batch leading dim must divide evenly)."""
    loss_fn = make_loss_fn(cfg, sc, q_chunk, loss_chunk)
    sched = wsd_schedule if cfg.wsd_schedule else cosine_schedule

    def train_step(params, opt_state, batch):
        def split(x):
            return x.reshape((micro_steps, x.shape[0] // micro_steps)
                             + tuple(x.shape[1:]))
        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            gsum, lsum = carry
            lv, g = jax.value_and_grad(loss_fn)(params, mb)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
            return (gsum, lsum + lv), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (gsum, lsum), _ = jax.lax.scan(body, (zeros, jnp.zeros(())), micro)
        grads = jax.tree.map(lambda g: g / micro_steps, gsum)
        if grad_transform is not None:
            grads = grad_transform(grads)
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        lr = sched(opt_state["count"] + 1, peak_lr=peak_lr, warmup=warmup,
                   total=total_steps)
        params, opt_state = adamw_update(opt_cfg, grads, opt_state, params,
                                         lr)
        return params, opt_state, {"loss": lsum / micro_steps,
                                   "grad_norm": gnorm, "lr": lr}

    return train_step
