"""Full-graph GCN training through the program's trainer.

Set-up builds one `ElasticGNNTrainer` (the object `launch/train.py
--gnn` drives) over the configuration's graph, with features, labels and
weights drawn from the seed, and drives it through its first three steps
on batches of distinct labelled vertices; the first compiles, the last
times one step.  The same trainer and state then run the window: step
after step on fresh batches until `seconds` have passed, and the window
ends when the last step's state is ready.  The host keeps about
`QUEUE_S` seconds of steps in flight (from 2 to `MAX_IN_FLIGHT` steps),
so that a pause of the whole process of a second or so, which the chip's
host shows now and then inside the runtime's dispatch of a step (with no
Python collection running), does not idle the device; the window
overruns `seconds` by at most that.  Here the window's loop departs from
`launch/train.py`, which reads each step's loss before it dispatches
the next: host work per step shows in the step time only where it
exceeds the device's.

`verify` compares those first three steps with the reference: each
step's loss, the norm of each leaf of the first gradient as the
optimizer received it (its first moment after step 1 over 1 - b1), and
the norm of each leaf's change over the three steps.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost, harness, reference
from bench.gen import inputs
from bench.gen.rmat import rmat_edges
from bench.harness import span

CHECKED_STEPS = 3
QUEUE_S = 4.0
MAX_IN_FLIGHT = 256


def graph_edges(cfg: Dict):
    return rmat_edges(cfg["num_vertices"], cfg["num_edges"],
                      cfg["graph_seed"], *cfg["rmat_abc"])


def batch(ctx: Dict, step: int) -> Dict[str, np.ndarray]:
    return {"nodes": inputs.node_batch(ctx["n"], ctx["wl"]["batch"],
                                       ctx["seed"], step)}


def inputs_only(cell: Dict, seed: int) -> Dict:
    """What the reference needs of a run: the graph's edges, the first
    weights on the host, and the names of the rest (drawn again)."""
    cfg, wl = cell["config"], cell["workload"]
    src, dst = graph_edges(cfg)
    return {"cell": cell, "wl": wl, "cfg": cfg, "seed": seed,
            "n": cfg["num_vertices"], "src": src, "dst": dst,
            "p0": jax.tree.map(np.asarray,
                               inputs.weights(cfg["dims"], seed))}


def setup(cell: Dict, seed: int) -> Dict:
    from repro.core.models import make_gnn_stack
    from repro.graphs.format import COOGraph
    from repro.launch.elastic_gnn import ElasticGNNTrainer
    from repro.training.optimizer import init_opt_state

    harness.matmul_precision(cell["workload"])
    t = time.perf_counter()
    ctx = inputs_only(cell, seed)
    graph_s = time.perf_counter() - t
    cfg, wl, n = ctx["cfg"], ctx["wl"], ctx["n"]
    dims, opt = cfg["dims"], wl["optimizer"]
    graph = COOGraph(n, ctx["src"], ctx["dst"]).gcn_normalized()
    x = inputs.features(n, dims[0], seed, cfg["feature_scale"])
    y = inputs.labels(n, dims[-1], seed)
    params = inputs.weights(dims, seed)
    layers = make_gnn_stack(cfg["model"], dims, backend=wl["backend"])
    for layer in layers:
        layer.cfg.training = True          # as launch/train.py sets it
    t = time.perf_counter()
    with span("bench.prepare"):
        trainer = ElasticGNNTrainer(
            layers=layers, graph=graph, x=x, y_true=y, hidden=dims[1],
            peak_lr=opt["peak_lr"], steps=opt["total_steps"])
    ctx.update(trainer=trainer, prepare_s=time.perf_counter() - t,
               backend=trainer.plan.backend,
               tile_format=trainer.plan.tile_format)
    state = (params, init_opt_state(params))
    losses = []
    t = time.perf_counter()
    for k in range(CHECKED_STEPS):
        t_step = time.perf_counter()
        p, o, m = trainer.step(*state, batch(ctx, k))
        state = (p, o)
        losses.append(float(m["loss"]))
        if k == 0:
            ctx["m1"] = jax.tree.map(np.asarray, o["m"])
    ctx["in_flight"] = min(MAX_IN_FLIGHT, max(2, int(np.ceil(
        QUEUE_S / (time.perf_counter() - t_step)))))
    ctx["losses"] = losses
    ctx["p3"] = jax.tree.map(np.asarray, state[0])
    ctx["setup_parts"] = {"graph_s": graph_s,
                          "prepare_s": ctx["prepare_s"],
                          "first_steps_s": time.perf_counter() - t}
    ctx["state"] = state
    e = graph.num_edges
    ctx["cost"] = {"flops_per_step": cost.gcn_train_step_flops(n, e, dims),
                   "entries": cost.merged_entries(graph.src, graph.dst, n),
                   "aggregate_widths": cost.aggregate_widths(dims),
                   "n": n}
    return ctx


def window(ctx: Dict, seconds: float) -> Dict:
    trainer = ctx["trainer"]
    params, opt = ctx.pop("state")
    losses: List = []
    pending: deque = deque()
    k = CHECKED_STEPS
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            b = batch(ctx, k)
            with span("bench.train.step"):
                params, opt, m = trainer.step(params, opt, b)
            losses.append(m["loss"])
            pending.append(m["loss"])
            k += 1
            if len(pending) > ctx["in_flight"]:
                pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((params, opt))
        t1 = time.perf_counter()
    steps = k - CHECKED_STEPS
    finite = np.isfinite(np.asarray(jax.device_get(losses), np.float64))
    ctx["state"] = (params, opt)
    return {"t0": t0, "window_s": t1 - t0, "steps": steps,
            "attempted": steps, "failed": int((~finite).sum()),
            "train_step_ms": (t1 - t0) * 1e3 / steps,
            "cost": ctx["cost"], "backend": ctx["backend"],
            "tile_format": ctx["tile_format"],
            "final_loss": float(losses[-1]),
            "in_flight": ctx["in_flight"],
            "setup_parts": ctx["setup_parts"]}


def release(ctx: Dict) -> None:
    """Drop every device array of the system under test."""
    for key in ("trainer", "state"):
        ctx.pop(key, None)
    gc.collect()


def reference_run(ctx: Dict, control: bool = False,
                  half_batch: bool = False):
    """The reference's first three steps from the same weights, inputs
    and batches: (losses, first moment after step 1, final params).
    `control` runs the control in its place, `half_batch` the planted
    fault."""
    cfg, wl, seed, n = ctx["cfg"], ctx["wl"], ctx["seed"], ctx["n"]
    dims = cfg["dims"]
    s, d, v = reference.normalize(ctx["src"], ctx["dst"], n)
    x = inputs.features(n, dims[0], seed, cfg["feature_scale"])
    y = inputs.labels(n, dims[-1], seed)
    batches = [batch(ctx, k)["nodes"] for k in range(CHECKED_STEPS)]
    dtype = reference.CONTROL_DTYPE if control else jnp.float32
    return reference.train(ctx["p0"], x, s, d, v, n, y, batches,
                           wl["optimizer"], dtype=dtype,
                           half_batch=half_batch)


def _norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64)))
            for a in jax.tree.leaves(tree)]


def leaf_gap(got: List[float], want: List[float],
             skip: List[bool]) -> float:
    """The worst leaf's |norm(got) - norm(want)|, over the larger of
    that leaf's reference norm and the median leaf's."""
    med = float(np.median(want))
    gaps = [abs(g - w) / max(w, med, 1e-30)
            for g, w, s in zip(got, want, skip) if not s]
    return max(gaps) if gaps else 0.0


def compare(ctx: Dict, got, want) -> Dict[str, float]:
    """The compared numbers of `got` (program or stand-in) against
    `want` (the reference), each a (losses, m1, p3) triple."""
    b1 = ctx["wl"]["optimizer"]["b1"]
    lp, mp, pp = got
    lr, mr, pr = want
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(lp, lr))
    g_got = [v / (1 - b1) for v in _norms(mp)]
    g_want = [v / (1 - b1) for v in _norms(mr)]
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: leave them out of the change
    med = float(np.median(g_want))
    skip = [g < 1e-3 * med for g in g_want]
    p0 = ctx["p0"]
    d_got = _norms(jax.tree.map(lambda a, b: a - b, pp, p0))
    d_want = _norms(jax.tree.map(lambda a, b: a - b, pr, p0))
    return {"loss_gap": loss_gap,
            "grad_gap": leaf_gap(g_got, g_want, [False] * len(g_want)),
            "change_gap": leaf_gap(d_got, d_want, skip)}


def verify(ctx: Dict) -> Dict[str, float]:
    got = (ctx["losses"], ctx["m1"], ctx["p3"])
    release(ctx)
    return compare(ctx, got, reference_run(ctx))
