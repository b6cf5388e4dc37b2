"""Expert-parallel MoE dispatch via all-to-all (GShard-style), inside
shard_map.

Why this exists (EXPERIMENTS.md SPerf, moonshot_v1_16b_a3b/train_4k):
under pjit auto-partitioning, scattering data-sharded tokens into a
model-sharded expert buffer lowers to *full-buffer all-reduces* —
17.5 TB/device/step.  The production dataflow routes tokens explicitly:

  1. each device routes its local tokens (top-k, capacity-bounded)
     into a per-expert send buffer (E, cap_loc, D);
  2. one all-to-all over the model axis moves each expert's slice to
     the device that owns it (experts are model-sharded);
  3. the owner runs the expert FFNs on (E_loc, M*cap_loc, D);
  4. the reverse all-to-all returns expert outputs to the token owners,
     which combine them with the router gates.

Collective bytes per layer: 2 x E x cap_loc x D — proportional to the
*local* token count, independent of the global batch.

Edge-centric note: this IS the EnGN aggregate stage on the token->expert
bipartite graph, executed with the paper's tiling discipline — tokens
(edges) are grouped by destination (expert interval), moved once, and
reduced densely at the owner.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.nn.config import ModelConfig


def _axes_tuple(ax):
    if ax is None:
        return ()
    return tuple(ax) if isinstance(ax, tuple) else (ax,)


def model_axis_size(mesh: Mesh, rules) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([shape.get(a, 1)
                        for a in _axes_tuple(rules.get("experts"))]))


def _local_dispatch(cfg, router, xf, cap, dtype):
    """Route local tokens: returns (buf (E, cap, D), combine info)."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_p = top_p.reshape(-1)
    order = jnp.argsort(flat_e)
    ge, gt, gp = flat_e[order], flat_t[order], flat_p[order]
    group_start = jnp.searchsorted(ge, jnp.arange(e))
    pos = jnp.arange(t * k) - group_start[ge]
    keep = pos < cap
    slot = jnp.where(keep, ge * cap + pos, e * cap)
    buf = jnp.zeros((e * cap + 1, d), dtype).at[slot].set(
        xf[gt], mode="drop")[:-1].reshape(e, cap, d)
    return buf, (slot, gt, gp, keep)


def _local_combine(out_buf, info, t, d, dtype):
    """Scatter expert outputs back to local tokens with gate weights."""
    slot, gt, gp, keep = info
    e_cap = out_buf.shape[0] * out_buf.shape[1]
    flat = out_buf.reshape(e_cap, d)
    contrib = flat[jnp.minimum(slot, e_cap - 1)]
    contrib = contrib * (gp * keep).astype(dtype)[:, None]
    return jnp.zeros((t, d), dtype).at[gt].add(contrib)


def moe_ffn_a2a(cfg: ModelConfig, p, x: jnp.ndarray, mesh: Mesh, rules,
                capacity_factor: float = 1.25) -> jnp.ndarray:
    """x: (B, S, D) global -> (B, S, D).  Must be called under the mesh
    (inside the jit that pjit-partitions the step)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    ex_axes = _axes_tuple(rules.get("experts"))
    ex_ax = ex_axes[0]                       # single model axis in practice
    m = model_axis_size(mesh, rules)
    assert e % m == 0, (e, m)

    # mirror the Constrainer's divisibility fallback: only shard dims
    # that divide their mesh-axis size
    bt_axes = _axes_tuple(rules.get("batch"))
    if b % max(_mesh_size(mesh, bt_axes), 1) != 0:
        bt_axes = ()
    seq_axes = _axes_tuple(rules.get("seq"))
    if s % max(_mesh_size(mesh, seq_axes), 1) != 0:
        seq_axes = ()
    b_loc = b // max(_mesh_size(mesh, bt_axes), 1)
    s_loc = s // max(_mesh_size(mesh, seq_axes), 1)
    t_loc = b_loc * s_loc
    cap = max(1, int(np.ceil(t_loc * k / e * capacity_factor)))

    x_spec = P(bt_axes if bt_axes else None,
               seq_axes[0] if seq_axes else None, None)
    w_spec = P(ex_ax, None, None)            # experts live on the model axis
    r_spec = P(None, None)                   # router replicated (small)

    def body(router, wg, wu, wd, xs):
        bl, sl, _ = xs.shape
        xf = xs.reshape(bl * sl, d)
        buf, info = _local_dispatch(cfg, router, xf, cap, xs.dtype)
        # (E, cap, D) -> (M, E_loc, cap, D) -> a2a -> (M, E_loc, cap, D)
        # where dim0 now indexes the *source* model-rank.
        e_loc = e // m
        send = buf.reshape(m, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, ex_ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        # expert compute on (E_loc, M*cap, D)
        h_in = recv.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)
        act = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", h_in,
                                      wg.astype(xs.dtype)))
               * jnp.einsum("ecd,edf->ecf", h_in, wu.astype(xs.dtype)))
        h_out = jnp.einsum("ecf,efd->ecd", act, wd.astype(xs.dtype))
        # reverse path
        back = h_out.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, ex_ax, split_axis=0, concat_axis=0,
                                 tiled=False)
        out_buf = ret.reshape(e, cap, d)
        out = _local_combine(out_buf, info, bl * sl, d, xs.dtype)
        return out.reshape(bl, sl, d)

    # Decode (seq unsharded): every model-rank holds the same tokens, so
    # after the a2a round-trip the output is semantically replicated over
    # the model axis — but that cannot be statically inferred through
    # all_to_all, so the vma check must be disabled for that case.  The
    # train path (seq sharded) keeps the check (and its autodiff psum
    # bookkeeping, verified in tests/test_moe_a2a.py).
    check = bool(seq_axes)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(r_spec, w_spec, w_spec, w_spec, x_spec),
                       out_specs=x_spec, check_vma=check)
    out = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)

    if cfg.n_shared_experts:
        from repro.nn.layers import mlp, no_sc
        out = out + mlp(p["shared"], x.reshape(b * s, d), no_sc
                        ).reshape(b, s, d)
    return out


def _mesh_size(mesh: Mesh, axes) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([shape.get(a, 1) for a in axes]))
