"""Train-step factory for the GNN path: loss -> grads -> clip -> AdamW
on a cosine schedule, with the optimizer under its trace scope.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

from repro.trace import OPTIMIZER, scope
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      clip_by_global_norm)
from repro.training.schedule import cosine_schedule


def make_gnn_train_step(loss_fn: Callable, *,
                        opt_cfg: Optional[AdamWConfig] = None,
                        peak_lr: float = 5e-3, warmup: int = 20,
                        total_steps: int = 100, jit: bool = True):
    """Train-step factory for the GNN path (launch/train.py --gnn):
    loss -> grads -> clip -> AdamW on a cosine schedule, for a
    `loss_fn(params, batch)` over any aggregation backend.  That
    includes the streamed out-of-core "tiled" backend: its aggregate is
    a custom_vjp host callback whose backward re-streams the transposed
    tile store (core/tiled.py, DESIGN.md C9), so the whole step still
    jits and grads flow to the parameters.

    Extra positional arguments of the step (`step(params, opt, batch,
    *consts)`) reach `loss_fn(params, batch, *consts)` unchanged: pass
    device-resident graph operands and features that way rather than
    closing over them, so they are program parameters, not constants."""
    opt_cfg = opt_cfg if opt_cfg is not None else AdamWConfig(
        weight_decay=0.01)

    def train_step(params, opt_state, batch, *consts):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, *consts)
        with scope(OPTIMIZER):
            grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
            lr = cosine_schedule(opt_state["count"] + 1, peak_lr=peak_lr,
                                 warmup=warmup, total=total_steps)
            params, opt_state = adamw_update(opt_cfg, grads, opt_state,
                                             params, lr)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr": lr}

    return jax.jit(train_step) if jit else train_step
