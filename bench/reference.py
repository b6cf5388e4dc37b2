"""The plain reference that decides `correct`.

A 2-layer GCN as Kipf and Welling define it (arXiv:1609.02907, Eq. 2),
written in straightforward `jax.numpy` and independent of the program:

    A~ = A + I,  D~ = in-degrees of A~,  A^ = D~^-1/2 A~ D~^-1/2
    H_{l+1} = relu(A^ H_l W_l)

The edge list is a multigraph: a repeated edge counts once per copy in
A~ and in D~, as the program's generator emits it.  One departure from
the paper follows the program: the last layer is a ReLU too, and its
output is the logit vector (the paper ends in a softmax without ReLU).
Dropout is left out, as the program has none.

Training is the mean cross-entropy over a batch of labelled vertices,
gradients clipped to a global norm, and AdamW with decoupled weight
decay (Loshchilov and Hutter) on a linear-warmup cosine schedule; the
hyper-parameters come from the workload file.

On a TPU a float32 matmul runs in bfloat16 unless told otherwise, so
the reference runs every matmul at HIGHEST precision.  The same code is
the control, one step of precision below what every workload states
(float32 at the default precision, one bfloat16 pass on the MXU, or at
"high", three passes): it runs in `CONTROL_DTYPE`, bfloat16, for the
features, edge weights, weights, every activation, the loss, the
gradients, the optimizer's moments and its update.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

CONTROL_DTYPE = jnp.bfloat16


def normalize(src: np.ndarray, dst: np.ndarray, n: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(src, dst, val) of A^: self loops appended, weights
    d(src)^-1/2 d(dst)^-1/2 from the in-degrees of A~, in float64 then
    rounded to float32."""
    loops = np.arange(n, dtype=np.int32)
    s = np.concatenate([np.asarray(src, np.int32), loops])
    d = np.concatenate([np.asarray(dst, np.int32), loops])
    deg = np.bincount(d, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    return s, d, (dinv[s] * dinv[d]).astype(np.float32)


EDGE_BLOCK = 1 << 21


def edge_blocks(src, dst, val, block: int = EDGE_BLOCK):
    """The edge list cut into (blocks, block) rows, padded with edges of
    weight 0 at vertex 0, so the aggregate never holds more than one
    block's gathered rows (the largest graph's (E, H) gather would not
    fit beside its gradients)."""
    e = int(np.shape(src)[0])
    block = min(block, max(e, 1))
    nb = -(-e // block)
    pad = nb * block - e

    def cut(a, fill):
        a = np.asarray(a)
        return np.concatenate([a, np.full(pad, fill, a.dtype)]
                              ).reshape(nb, block)
    return cut(src, 0), cut(dst, 0), cut(val, 0.0)


def aggregate(z, src, dst, val, n: int):
    """sum over edges of val * z[src] at dst, one edge block at a time;
    src/dst/val are (blocks, block) from `edge_blocks`."""
    def body(acc, blk):
        s, d, v = blk
        return acc + jax.ops.segment_sum(z[s] * v.astype(z.dtype)[:, None],
                                         d, num_segments=n), None

    acc = jnp.zeros((n, z.shape[1]), z.dtype)
    return jax.lax.scan(body, acc, (src, dst, val))[0]


def forward(params: Sequence[Dict], x, src, dst, val, n: int,
            dtype=jnp.float32):
    """Logits of every vertex: relu(A^ h W) per layer; the edges come
    as blocks from `edge_blocks`."""
    h = x.astype(dtype)
    for p in params:
        z = jnp.dot(h, p["w"].astype(dtype), precision=HIGHEST)
        h = jax.nn.relu(aggregate(z, src, dst, val, n))
    return h


def loss(params, x, src, dst, val, n, nodes, y, dtype=jnp.float32,
         half_batch: bool = False):
    """Mean cross-entropy over the batch vertices `nodes` with labels
    `y[nodes]`.  `half_batch=True` is a planted fault: the mean over
    the first half of the batch only."""
    if half_batch:
        nodes = nodes[: nodes.shape[0] // 2]
    logits = forward(params, x, src, dst, val, n, dtype)[nodes]
    ll = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(ll, y[nodes][:, None], 1))


def lr_at(step, opt: Dict):
    """Linear warmup to `peak_lr` over `warmup` steps, then a cosine
    down to `final_frac` of it at `total_steps`."""
    step = jnp.asarray(step, jnp.float32)
    warm = opt["peak_lr"] * step / max(opt["warmup"], 1)
    prog = jnp.clip((step - opt["warmup"])
                    / max(opt["total_steps"] - opt["warmup"], 1), 0, 1)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < opt["warmup"], warm, opt["peak_lr"] * cos)


def adamw(params, grads, m, v, count, opt: Dict):
    """One clipped AdamW update, in the parameters' dtype; weight decay
    on matrices only."""
    leaves_g, tree = jax.tree.flatten(grads)
    dt = leaves_g[0].dtype
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves_g))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    count = count + 1
    c = count.astype(jnp.float32)
    lr = lr_at(count, opt).astype(dt)
    bc1, bc2 = (1 - opt["b1"] ** c).astype(dt), (1 - opt["b2"] ** c).astype(dt)
    b1, b2 = opt["b1"], opt["b2"]
    new_p, new_m, new_v = [], [], []
    for p, g, m_, v_ in zip(tree.flatten_up_to(params), leaves_g,
                            tree.flatten_up_to(m), tree.flatten_up_to(v)):
        g = g * scale.astype(dt)
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        step = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["eps"])
        decay = opt["weight_decay"] if p.ndim > 1 else 0.0
        new_p.append(p - lr * (step + decay * p))
        new_m.append(m_)
        new_v.append(v_)
    return (tree.unflatten(new_p), tree.unflatten(new_m),
            tree.unflatten(new_v), count)


def train(params0: List[Dict], x, src, dst, val, n, y,
          batches: Sequence[np.ndarray], opt: Dict, dtype=jnp.float32,
          half_batch: bool = False):
    """Run len(batches) steps from `params0`.  Returns (losses, the
    optimizer's first moment after step 1, the parameters after the
    last step), all on the host."""
    graph = tuple(jnp.asarray(a) for a in edge_blocks(src, dst, val))

    @jax.jit
    def step(p, m, v, count, nodes, x, y, graph):
        lv, g = jax.value_and_grad(loss)(p, x, *graph, n, nodes, y,
                                         dtype, half_batch)
        p, m, v, count = adamw(p, g, m, v, count, opt)
        return p, m, v, count, lv

    p = jax.tree.map(lambda a: jnp.asarray(a, dtype), params0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    count = jnp.zeros((), jnp.int32)
    losses, m1 = [], None
    with jax.default_matmul_precision("highest"):
        for nodes in batches:
            p, m, v, count, lv = step(p, m, v, count, jnp.asarray(nodes),
                                      x, y, graph)
            losses.append(float(lv))
            if m1 is None:
                m1 = jax.tree.map(_host, m)
    return losses, m1, jax.tree.map(_host, p)


def _host(a) -> np.ndarray:
    return np.asarray(a.astype(jnp.float32))


def logits(params, x, src, dst, val, n, dtype=jnp.float32) -> np.ndarray:
    """The full-graph forward, on the host."""
    fn = jax.jit(forward, static_argnums=(5, 6))
    blocks = tuple(jnp.asarray(a) for a in edge_blocks(src, dst, val))
    with jax.default_matmul_precision("highest"):
        out = fn(params, x, *blocks, n, dtype)
    return np.asarray(out.astype(jnp.float32))
