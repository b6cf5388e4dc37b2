"""Fused feature-extraction / update tile kernel: Y = act(X @ W + b).

The paper's feature-extraction and update stages are dense matmuls followed
by an XPE epilogue (bias, activation, rounding).  On TPU the epilogue is
fused into the matmul's final reduction step so the activation never makes
a round trip to HBM.

Grid: (N/Tn, H/Th, F/Tf) with the reduction axis innermost so the output
tile is revisited on consecutive steps (accumulate in VMEM, epilogue on the
last step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(act: str, nsteps_f: int):
    def kernel(x_ref, w_ref, b_ref, y_ref):
        kf = pl.program_id(2)
        prev = jnp.where(kf == 0, jnp.zeros_like(y_ref), y_ref[...])
        acc = prev + jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32)
        # epilogue on the final reduction step
        done = kf == nsteps_f - 1
        out = acc + b_ref[...][None, :]
        if act == "relu":
            out = jax.nn.relu(out)
        elif act == "sigmoid":
            out = jax.nn.sigmoid(out)
        elif act == "tanh":
            out = jnp.tanh(out)
        y_ref[...] = jnp.where(done, out, acc)
    return kernel


def fused_linear_act_kernel(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                            *, act: str = "relu", tn: int = 256,
                            th: int = 256, tf: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    n, f = x.shape
    f2, h = w.shape
    assert f == f2
    tn, th, tf = min(tn, n), min(th, h), min(tf, f)
    assert n % tn == 0 and h % th == 0 and f % tf == 0, (n, h, f, tn, th, tf)
    grid = (n // tn, h // th, f // tf)
    return pl.pallas_call(
        _make_kernel(act, grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, tf), lambda i, j, k: (i, k)),
            pl.BlockSpec((tf, th), lambda i, j, k: (k, j)),
            pl.BlockSpec((th,), lambda i, j, k: (j,)),
        ],
        out_specs=pl.BlockSpec((tn, th), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        name="feature_update",
        interpret=interpret,
    )(x, w, b)
