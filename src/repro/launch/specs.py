"""ShapeDtypeStruct input stand-ins + sharding specs per (arch x shape).

The four assigned input shapes:
    train_4k    seq=4096   global_batch=256   -> train_step
    prefill_32k seq=32768  global_batch=32    -> prefill_step
    decode_32k  seq=32768  global_batch=128   -> serve_step (1 new token)
    long_500k   seq=524288 global_batch=1     -> serve_step, sub-quadratic
                                                 archs only
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.nn.config import ModelConfig
from repro.nn import transformer as T
from repro.distributed.sharding import batch_pspec, mesh_shape_dict
from repro.nn.sharding import make_rules

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


def shape_applicable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not cfg.subquadratic:
        return False, "skipped: full-attention arch (quadratic at 500k)"
    return True, ""


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# ---------------------------------------------------------------- inputs
def train_batch_specs(cfg: ModelConfig, seq: int, batch: int):
    """(ShapeDtypeStruct pytree, logical pspec pytree builder)."""
    b = {
        "tokens": sds((batch, seq), jnp.int32),
        "labels": sds((batch, seq), jnp.int32),
    }
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = sds((batch, cfg.n_patches, cfg.d_model),
                                     jnp.bfloat16)
    if cfg.family == "encdec":
        extras["frames"] = sds((batch, seq, cfg.d_model), jnp.bfloat16)
    if extras:
        b["extras"] = extras
    return b


def train_batch_pspecs(cfg: ModelConfig, mesh: Mesh, rules=None):
    rules = rules or make_rules(mesh)
    b = {
        "tokens": batch_pspec(mesh, 2, seq_axis=1, rules=rules),
        "labels": batch_pspec(mesh, 2, seq_axis=1, rules=rules),
    }
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = batch_pspec(mesh, 3, rules=rules)
    if cfg.family == "encdec":
        extras["frames"] = batch_pspec(mesh, 3, seq_axis=1, rules=rules)
    if extras:
        b["extras"] = extras
    return b


def _mesh_axis_size(mesh_shape, ax):
    import numpy as np
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        return int(np.prod([mesh_shape.get(a, 1) for a in ax]))
    return mesh_shape.get(ax, 1)


def _pspec_from_logical(shape, logical, mesh_shape, rules):
    used = set()
    out = []
    for dim, ax in zip(shape, logical):
        mesh_ax = rules.get(ax) if ax is not None else None
        key = tuple(mesh_ax) if isinstance(mesh_ax, tuple) else mesh_ax
        if (mesh_ax is None or dim % _mesh_axis_size(mesh_shape, mesh_ax) != 0
                or key in used):
            out.append(None)
        else:
            out.append(mesh_ax)
            used.add(key)
    return P(*out)


def decode_state_logical(cfg: ModelConfig):
    """Logical axes per decode-state leaf kind."""
    return {
        "k": (None, "batch", "seq", None, None),
        "v": (None, "batch", "seq", None, None),
        "mk": (None, "batch", "seq", None, None),
        "mv": (None, "batch", "seq", None, None),
        "conv": (None, "batch", None, "mlp"),
        "ssm": (None, "batch", "mlp", None),
        "pos": (),
    }


def decode_state_specs(cfg: ModelConfig, batch: int, max_len: int):
    state = jax.eval_shape(
        lambda: T.init_decode_state(cfg, batch, max_len))
    return state


def decode_state_pspecs(cfg: ModelConfig, state_sds, mesh: Mesh, rules=None):
    rules = rules or make_rules(mesh)
    ms = mesh_shape_dict(mesh)
    logical = decode_state_logical(cfg)

    def leaf_spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        la = logical.get(name)
        if la is None or len(leaf.shape) == 0:
            return P()
        return _pspec_from_logical(leaf.shape, la, ms, rules)

    return jax.tree_util.tree_map_with_path(leaf_spec, state_sds)
