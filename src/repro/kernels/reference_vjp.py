"""Reverse rule for the Mosaic aggregate kernels.

`pallas_call` has no transpose rule, so `jax.grad` through a dispatcher
that picked a Mosaic kernel raises `NotImplementedError` — on a TPU
that is every blocked or fused training step.  Each dispatcher wraps its
kernel here: the kernel runs the forward pass, and the backward pass is
the VJP of the dispatcher's own XLA formulation of the identical
dataflow (`blocked_spmm_xla`, `packed_spmm_xla`, `_fused_xla`).  Only
the trailing feature / weight operands get cotangents; the leading
graph carriers (tiles, indices, edge weights) are constants of the plan.
"""
from __future__ import annotations

from typing import Callable

import jax


def with_reference_vjp(kernel: Callable, reference: Callable,
                       num_consts: int) -> Callable:
    """`kernel(*consts, *diff)` forward, VJP of `reference(*consts,
    *diff)` backward, where the first `num_consts` positional operands
    are constants.  Bind static options with `functools.partial`."""

    @jax.custom_vjp
    def fn(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        consts, diff = args[:num_consts], args[num_consts:]
        _, vjp = jax.vjp(lambda *d: reference(*consts, *d), *diff)
        return (None,) * num_consts + tuple(vjp(g))

    fn.defvjp(fwd, bwd)
    return fn
