#!/usr/bin/env python3
"""Bring the EnGN GCN path up on a TPU and check every answer it gives.

    python chip_smoke.py              # one chip: forward, training, serving
    python chip_smoke.py --chips 4    # four chips: the sharded ring only

One process imports JAX once and drives the main path through the entry
points a user calls (`prepare_graph` + `EnGNLayer.apply`, the
`launch/train.py` GNN trainer, `ServingPipeline`).  The graph is the
pubmed stand-in at its Table-5 size and width — 19,717 vertices, 88,651
edges, F = 500, 3 classes — and the model a 2-layer GCN at the
launcher's default hidden width, with random weights from a fixed seed.

One chip, three phases:
  forward   segment, blocked on dense tiles, blocked on packed tiles,
            and the streamed tiled backend under a device budget (its
            chunk queue under the afu stage order, the per-chunk stream
            under fau), each against a float64 numpy oracle of the same
            forward;
  training  a few trainer steps on segment and on blocked: finite
            losses, and first-step gradients that agree;
  serving   a ServingPipeline with exact L-hop extraction answers zipf
            requests; every response is ok and equals the full-graph
            forward's rows.
With `--chips 4` only the sharded ring runs (forward passes and trainer
steps with ring_shards=4), against one-chip segment; its program must
hold a collective-permute and its four stripes must sit on four devices.

Every line before the last is a JSON record of one check.  Wall times
are bring-up times, compilation included — not benchmarks.  The last
line is `{"ok": true, "device": {...}}`; any failed check exits non-zero
before it, a watchdog ends a phase that hangs, and without a TPU the
script exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import sys
import time
from pathlib import Path
from typing import Any, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.engn import prepare_graph  # noqa: E402
from repro.core.models import apply_stack, init_stack, make_gnn_stack  # noqa: E402
from repro.core.plan import split_arrays  # noqa: E402
from repro.graphs.generate import (make_dataset, random_features,  # noqa: E402
                                   zipf_traffic)
from repro.kernels.chunk_queue.ops import default_impl  # noqa: E402

SEED = 0
HIDDEN = 32             # launch/train.py's default --gnn-hidden
TRAIN_STEPS = 3
REQUESTS = 64
PHASE_TIMEOUT_S = 420   # watchdog: a phase that runs longer is hung
# A streaming budget far below the chip's 16 GB: the tiled executor must
# stream the graph, while its chunk queue still fits.
TILED_BUDGET = 256 << 20

# TPU matmuls round f32 operands to bf16 by default (8-bit mantissa,
# relative error 2^-9 per operand).  Each layer's extraction matmul, and
# on the blocked path its tile contraction, adds about 2^-8 of the
# layer's output magnitude; 2e-2 of the largest output leaves a 5x
# margin over two layers.  A dropped or doubled edge moves its row by the
# whole edge contribution, which is of the output's own magnitude.
FORWARD_TOL = 2e-2
# The backward matmuls round the same way; the blocked backward is the
# XLA VJP of the tiled formulation, the segment backward a scatter-add.
GRAD_TOL = 2e-2
# Serving runs the same segment program on a subgraph: the operands are
# rounded identically and only the accumulation order differs.
SERVE_TOL = 1e-3

_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def record(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@dataclasses.dataclass
class Problem:
    """The graph, features, weights and float64 oracle every phase
    shares; `max_vertices` / `max_edges` / `feature_dim` cut it down
    for CPU tests."""
    graph: Any
    x: np.ndarray
    f: int
    classes: int
    params: list
    oracle: np.ndarray
    max_vertices: Optional[int] = None
    max_edges: Optional[int] = None
    feature_dim: Optional[int] = None


def make_problem(max_vertices=None, max_edges=None,
                 feature_dim=None) -> Problem:
    g, f, classes = make_dataset("pubmed", seed=SEED,
                                 max_vertices=max_vertices,
                                 max_edges=max_edges,
                                 feature_dim=feature_dim)
    gn = g.gcn_normalized()
    x = random_features(gn.num_vertices, f, seed=SEED)
    params = init_stack(make_gnn_stack("gcn", [f, HIDDEN, classes]),
                        jax.random.key(SEED))
    return Problem(gn, x, f, classes, params, oracle(gn, x, params),
                   max_vertices, max_edges, feature_dim)


def oracle(g, x: np.ndarray, params) -> np.ndarray:
    """The GCN forward in float64 on the host: per layer
    relu(sum over in-edges of val * (x W)[src])."""
    h = x.astype(np.float64)
    w_edge = g.weights().astype(np.float64)[:, None]
    for p in params:
        xw = h @ np.asarray(p["w"], np.float64)
        agg = np.zeros((g.num_vertices, xw.shape[1]))
        np.add.at(agg, g.dst, w_edge * xw[g.src])
        h = np.maximum(agg, 0.0)
    return h


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def custom_calls(compiled) -> int:
    return compiled.as_text().count(_CUSTOM_CALL)


def compile_forward(layers, params, plan, x):
    """AOT-compile the stack forward with the plan's device operands as
    arguments; returns (compiled, args)."""
    arrays, rebuild = split_arrays(plan.carrier)
    fn = jax.jit(lambda ps, arrs, xx: apply_stack(layers, ps,
                                                  rebuild(arrs), xx))
    args = (params, arrays, jnp.asarray(x))
    return fn.lower(*args).compile(), args


def gcn_layers(prob: Problem, backend: str, **cfg):
    layers = make_gnn_stack("gcn", [prob.f, HIDDEN, prob.classes],
                            backend=backend)
    for layer in layers:
        for k, v in cfg.items():
            setattr(layer.cfg, k, v)
    return layers


# ----------------------------------------------------------------------
# phase (a): forward on every single-chip backend
# ----------------------------------------------------------------------

def forward_resident(prob: Problem, backend: str, **cfg) -> np.ndarray:
    t0 = time.perf_counter()
    layers = gcn_layers(prob, backend, **cfg)
    plan = prepare_graph(prob.graph, layers[0].cfg, out_dim=HIDDEN)
    check(plan.backend == backend, f"{backend} plan landed on "
          f"{plan.backend}")
    compiled, args = compile_forward(layers, prob.params, plan, prob.x)
    y = np.asarray(jax.block_until_ready(compiled(*args)))
    n_custom = custom_calls(compiled)
    err = rel_err(y, prob.oracle)
    record(phase="forward", backend=plan.backend,
           tile_format=plan.tile_format, tpu_custom_calls=n_custom,
           rel_err=err, tol=FORWARD_TOL,
           bringup_wall_s=time.perf_counter() - t0)
    check(np.all(np.isfinite(y)), f"{backend}: non-finite output")
    check(err <= FORWARD_TOL, f"{backend}/{plan.tile_format}: error "
          f"{err} > {FORWARD_TOL}")
    if on_tpu() and backend == "blocked":
        check(n_custom > 0, "blocked program holds no tpu_custom_call")
    return y


def forward_tiled(prob: Problem) -> None:
    """The streamed executor under `TILED_BUDGET`: the afu order
    aggregates raw features through the device-resident chunk queue
    (the Mosaic walker on TPU), the fau order streams extracted
    features chunk by chunk through the per-chunk step."""
    layers = gcn_layers(prob, "tiled", tile_format="packed",
                        device_budget_bytes=TILED_BUDGET)
    plan = prepare_graph(prob.graph, layers[0].cfg, out_dim=HIDDEN)
    check(plan.backend == "tiled", f"tiled plan landed on {plan.backend}")
    check(plan.streaming_mode == "chunk_queue",
          f"tiled plan streams by {plan.streaming_mode}, not the queue")
    ex = plan.carrier["tiled_exec"]
    for order in ("afu", "fau"):
        t1 = time.perf_counter()
        for layer in layers:
            layer.cfg.stage_order = order
        ex.reset_stats()
        y = np.asarray(apply_stack(layers, prob.params, plan, prob.x))
        st = ex.stats
        err = rel_err(y, prob.oracle)
        record(phase="forward", backend="tiled", tile_format=plan.tile_format,
               stage_order=order, tile=ex.store.tile,
               queue_launches=st.queue_launches, chunk_steps=st.steps,
               impl=default_impl(), rel_err=err,
               tol=FORWARD_TOL, bringup_wall_s=time.perf_counter() - t1)
        check(np.all(np.isfinite(y)), f"tiled/{order}: non-finite output")
        check(err <= FORWARD_TOL, f"tiled/{order}: error {err} > "
              f"{FORWARD_TOL}")
        if order == "afu":
            check(st.queue_launches > 0 and st.steps == 0,
                  "afu aggregate did not run on the chunk queue")
        else:
            check(st.steps > 0, "fau aggregate did not stream chunks")


def phase_forward(prob: Problem) -> None:
    forward_resident(prob, "segment")
    forward_resident(prob, "blocked", tile_format="dense")
    forward_resident(prob, "blocked", tile_format="packed")
    forward_tiled(prob)


# ----------------------------------------------------------------------
# phase (b): trainer steps
# ----------------------------------------------------------------------

def build_trainer(prob: Problem, backend: str, **kw):
    from repro.launch.train import build_gnn
    return build_gnn(model="gcn", dataset="pubmed", backend=backend,
                     steps=TRAIN_STEPS, seed=SEED,
                     max_vertices=prob.max_vertices,
                     max_edges=prob.max_edges,
                     feature_dim=prob.feature_dim, **kw)


def train(prob: Problem, backend: str, **kw):
    """Build the trainer, take its first-step gradients, then run
    `TRAIN_STEPS` steps; returns the gradients."""
    t0 = time.perf_counter()
    step, state, data, plan, aux = build_trainer(prob, backend, **kw)
    trainer = aux["trainer"]
    batch = next(data)
    args = (state["params"], batch) + tuple(trainer.consts)
    fwd = jax.jit(trainer.loss_fn).lower(*args).compile()
    grad = jax.jit(jax.grad(trainer.loss_fn)).lower(*args).compile()
    grads = jax.block_until_ready(grad(*args))
    n_fwd, n_grad = custom_calls(fwd), custom_calls(grad)
    ps, opt, losses = state["params"], state["opt"], []
    data.seek(0)
    for _ in range(TRAIN_STEPS):
        ps, opt, m = step(ps, opt, next(data))
        losses.append(float(m["loss"]))
    record(phase="training", backend=plan.backend,
           tile_format=plan.tile_format, shards=plan.meta.get("shards"),
           losses=losses, fwd_tpu_custom_calls=n_fwd,
           backward_tpu_custom_calls=n_grad - n_fwd,
           backward=("pallas" if n_grad > n_fwd else "xla"),
           bringup_wall_s=time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"{backend}: non-finite loss")
    if on_tpu() and backend == "blocked":
        check(n_fwd > 0, "blocked training forward holds no "
              "tpu_custom_call")
    return grads, plan


def compare_grads(name: str, got, want) -> None:
    errs = [rel_err(a, b) for a, b in zip(jax.tree.leaves(got),
                                          jax.tree.leaves(want))]
    record(phase="training", compare=name, grad_rel_err=max(errs),
           tol=GRAD_TOL)
    check(max(errs) <= GRAD_TOL, f"{name}: first-step gradients differ "
          f"by {max(errs)} > {GRAD_TOL}")


def phase_training(prob: Problem) -> None:
    g_seg, _ = train(prob, "segment")
    g_blk, _ = train(prob, "blocked")
    compare_grads("blocked vs segment", g_blk, g_seg)


# ----------------------------------------------------------------------
# phase (c): serving
# ----------------------------------------------------------------------

def phase_serving(prob: Problem) -> None:
    from repro.serving import GNNServingEngine, ServingConfig, ServingPipeline
    t0 = time.perf_counter()
    layers = gcn_layers(prob, "segment")
    plan = prepare_graph(prob.graph, layers[0].cfg, out_dim=HIDDEN)
    compiled, args = compile_forward(layers, prob.params, plan, prob.x)
    full = np.asarray(compiled(*args))
    engine = GNNServingEngine(
        prob.graph, prob.x, layers, prob.params,
        ServingConfig(batch_size=128, num_hops=len(layers), fanout=None))
    sample = zipf_traffic(prob.graph.degrees(), seed=SEED)
    rng = np.random.default_rng(SEED)
    wanted = {rid: sample(int(rng.integers(1, 17)))
              for rid in range(REQUESTS)}
    with ServingPipeline(engine) as pipe:
        for rid, ids in wanted.items():
            pipe.submit(rid, ids)
        responses = pipe.drain()
    statuses = sorted({r.status for r in responses})
    errs = [rel_err(r.outputs, full[wanted[r.rid]]) for r in responses
            if r.status == "ok"]
    record(phase="serving", requests=REQUESTS, responses=len(responses),
           statuses=statuses, rel_err=max(errs) if errs else None,
           tol=SERVE_TOL, compiles=engine.stats.get("compiles"),
           bringup_wall_s=time.perf_counter() - t0)
    check(len(responses) == REQUESTS and statuses == ["ok"],
          f"serving answered {len(responses)}/{REQUESTS} with {statuses}")
    check(max(errs) <= SERVE_TOL, f"serving rows differ from the full "
          f"forward by {max(errs)} > {SERVE_TOL}")


# ----------------------------------------------------------------------
# four chips: the sharded ring against one-chip segment
# ----------------------------------------------------------------------

def phase_ring(prob: Problem, shards: int = 4, passes: int = 3) -> None:
    t0 = time.perf_counter()
    layers = gcn_layers(prob, "ring", ring_shards=shards)
    plan = prepare_graph(prob.graph, layers[0].cfg, out_dim=HIDDEN)
    check(plan.backend == "ring" and plan.meta["shards"] == shards,
          f"ring plan landed on {plan.backend}")
    stripes = plan.carrier["ring_operands"][0]
    devices = {s.device for s in stripes.addressable_shards}
    compiled, args = compile_forward(layers, prob.params, plan, prob.x)
    hlo = compiled.as_text()
    for _ in range(passes):
        y = np.asarray(jax.block_until_ready(compiled(*args)))
    seg = gcn_layers(prob, "segment")
    seg_plan = prepare_graph(prob.graph, seg[0].cfg, out_dim=HIDDEN)
    seg_c, seg_args = compile_forward(seg, prob.params, seg_plan, prob.x)
    y_seg = np.asarray(seg_c(*seg_args))
    err_seg, err_ref = rel_err(y, y_seg), rel_err(y, prob.oracle)
    record(phase="ring", shards=shards, tile_format=plan.tile_format,
           stripe_devices=sorted(str(d) for d in devices),
           collective_permutes=hlo.count("collective-permute"),
           rel_err_vs_segment=err_seg, rel_err=err_ref, tol=FORWARD_TOL,
           bringup_wall_s=time.perf_counter() - t0)
    check(len(devices) == shards, f"ring stripes sit on {len(devices)} "
          f"devices, not {shards}")
    check("collective-permute" in hlo, "ring program holds no "
          "collective-permute")
    check(err_seg <= FORWARD_TOL and err_ref <= FORWARD_TOL,
          f"ring forward error {err_seg} (segment) / {err_ref} (oracle) "
          f"> {FORWARD_TOL}")
    g_ring, tplan = train(prob, "ring", ring_shards=shards)
    check(tplan.backend == "ring", "ring trainer left the ring")
    g_seg, _ = train(prob, "segment")
    compare_grads("ring vs segment", g_ring, g_seg)


# ----------------------------------------------------------------------

class Watchdog:
    """Exit non-zero, with every thread's traceback, if a phase runs
    past `timeout_s` — a nested dispatch that deadlocks must not hang
    the run."""

    def __init__(self, name: str, timeout_s: float = PHASE_TIMEOUT_S):
        self.name, self.timeout_s = name, timeout_s

    def __enter__(self):
        record(phase=self.name, status="start")
        faulthandler.dump_traceback_later(self.timeout_s, exit=True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        faulthandler.cancel_dump_traceback_later()
        if exc[0] is None:
            record(phase=self.name, status="passed",
                   bringup_wall_s=time.perf_counter() - self.t0)
        return False


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded ring on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    record(compile_cache=enable_compile_cache())
    with Watchdog("setup"):
        prob = make_problem()
        record(phase="setup", vertices=prob.graph.num_vertices,
               edges_with_self_loops=prob.graph.num_edges,
               feature_dim=prob.f,
               hidden=HIDDEN, classes=prob.classes)
    if args.chips == 4:
        with Watchdog("ring"):
            phase_ring(prob)
    else:
        for name, phase in (("forward", phase_forward),
                            ("training", phase_training),
                            ("serving", phase_serving)):
            with Watchdog(name):
                phase(prob)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
