"""Production training launcher: mesh + sharded step + data + fault
tolerance, assembled for any assigned architecture.

    # smoke-scale on CPU (1x1 mesh, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b \
        --smoke --steps 20

    # pod-scale (on a real TPU slice the same command, no --smoke;
    # the mesh comes from make_production_mesh / make_elastic_mesh):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2_72b \
        --batch 256 --seq 4096 --steps 1000 --ckpt-dir /ckpt/qwen2

    # GNN mode: train an EnGN stack on any aggregation backend,
    # including the sharded ring-tiled mesh backend (DESIGN.md C2) —
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 gives a CPU
    # stand-in mesh:
    PYTHONPATH=src python -m repro.launch.train --gnn gcn \
        --gnn-backend ring --dataset pubmed --steps 100

Features wired in: 2-D sharded train step (FSDP x TP + sequence
parallel), gradient accumulation for memory, WSD/cosine schedule per
config, atomic checkpoints with exact data replay, straggler logging,
elastic restart (auto-remesh to the surviving device count).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.data.pipeline import SyntheticTokenStream
from repro.distributed.fault import FaultConfig, FaultTolerantRunner
from repro.launch.mesh import make_elastic_mesh, single_device_mesh
from repro.launch import specs as SP
from repro.nn import transformer as T
from repro.nn.sharding import Constrainer, make_rules, param_pspecs
from repro.nn.train_step import make_grad_accum_train_step, make_train_step
from repro.training.optimizer import init_opt_state


def build(arch: str, *, smoke: bool, batch: int, seq: int, steps: int,
          micro_steps: int = 1, peak_lr: float = 3e-4,
          q_chunk: int = 512, loss_chunk: int = 256):
    """Assemble (mesh, sharded_step, init_state, data, cfg)."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    n_dev = len(jax.devices())
    mesh = single_device_mesh() if n_dev == 1 else make_elastic_mesh(n_dev)
    rules = make_rules(mesh)
    sc = Constrainer(mesh, rules)

    q_chunk = min(q_chunk, seq)
    loss_chunk = min(loss_chunk, seq)
    if micro_steps > 1:
        step = make_grad_accum_train_step(
            cfg, sc=sc, micro_steps=micro_steps, peak_lr=peak_lr,
            total_steps=steps, q_chunk=q_chunk, loss_chunk=loss_chunk)
    else:
        step = make_train_step(cfg, sc=sc, peak_lr=peak_lr,
                               total_steps=steps, q_chunk=q_chunk,
                               loss_chunk=loss_chunk)

    pparams = param_pspecs(cfg, mesh, rules)
    popt = {"m": pparams, "v": pparams, "count": P()}
    batch_ps = SP.train_batch_pspecs(cfg, mesh, rules)
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    jit_step = jax.jit(step,
                       in_shardings=(ns(pparams), ns(popt), ns(batch_ps)),
                       out_shardings=(ns(pparams), ns(popt), None),
                       donate_argnums=(0, 1))

    with mesh:
        params = jax.jit(
            lambda k: T.init_params(cfg, k),
            out_shardings=ns(pparams))(jax.random.key(0))
        opt = init_opt_state(params)

    data = SyntheticTokenStream(cfg.vocab_size, batch=batch, seq=seq,
                                seed=0)
    return mesh, jit_step, {"params": params, "opt": opt}, data, cfg


def build_gnn(*, model: str, dataset: str, backend: str, steps: int,
              hidden: int = 32, batch: int = 256,
              ring_shards=None, device_budget_bytes=None,
              max_vertices=None, max_edges=None, feature_dim=None,
              peak_lr: float = 5e-3, seed: int = 0,
              strike_limit: int = 3):
    """Assemble (train_step, init_state, data, graph_dict, aux) for a
    2-layer EnGN stack on any aggregation backend — the GNN counterpart
    of `build`.  `backend="ring"` trains on the sharded ring-tiled mesh
    (gradients flow through the ppermute rotation: the ring schedule is
    a scan, so reverse-mode AD works across shards).  A
    `device_budget_bytes` budget composes exactly as in inference:
    graphs whose training footprint (activations + cotangents) exceeds
    it spill to the streamed out-of-core "tiled" backend, which trains
    through its custom_vjp reverse path — the backward pass re-streams
    the same host tiles transposed (DESIGN.md C9), so the largest
    graphs are trainable under the same budget that serves them.

    The returned step is owned by an `ElasticGNNTrainer`
    (`aux["trainer"]`): its `on_failure`/`on_straggler` hooks re-mesh
    the ring to the surviving shard count and re-jit in place
    (DESIGN.md C13).

    The graph is the dataset's Table-5 stand-in at its published size
    and feature width; `max_vertices` / `max_edges` / `feature_dim` cut
    it down (tests on the CPU pass small sizes)."""
    from repro.core.engn import prepare_graph
    from repro.core.models import apply_stack, init_stack, make_gnn_stack
    from repro.data.pipeline import GraphNodeStream
    from repro.graphs.generate import make_dataset, random_features
    from repro.launch.elastic_gnn import ElasticGNNTrainer
    from repro.training.optimizer import init_opt_state

    g, f, classes = make_dataset(dataset, max_vertices=max_vertices,
                                 max_edges=max_edges, feature_dim=feature_dim)
    x = jnp.asarray(random_features(g.num_vertices, f, seed=seed))
    gn = g.gcn_normalized()

    # synthetic ground truth from a hidden teacher (segment reference)
    teacher = make_gnn_stack("gcn", [f, 16, classes])
    tp = init_stack(teacher, jax.random.key(42))
    gd_ref = prepare_graph(gn, teacher[0].cfg)
    y_true = jnp.argmax(apply_stack(teacher, tp, gd_ref, x), -1)

    num_rel = 1
    if model == "rgcn":
        # the bundled datasets are untyped: synthesise a deterministic
        # 3-type edge colouring so the typed stage contract (relation
        # tiles, per-relation weights) is exercised end to end
        import dataclasses
        import numpy as np
        num_rel = 3
        rel = ((gn.src.astype(np.int64) + gn.dst) % num_rel).astype(
            np.int32)
        gn = dataclasses.replace(gn, rel=rel, num_relations=num_rel)
    layers = make_gnn_stack(model, [f, hidden, classes], backend=backend,
                            num_relations=num_rel)
    for layer in layers:
        layer.cfg.ring_shards = ring_shards
        layer.cfg.device_budget_bytes = device_budget_bytes
        # price the budget gate for forward AND backward buffers, and
        # pre-size the streamed executor for the backward sweeps (C9)
        layer.cfg.training = True
    params = init_stack(layers, jax.random.key(seed))

    # a budget spill to gd["backend"] == "tiled" trains too: the
    # streamed aggregate carries a custom_vjp whose backward re-streams
    # the transposed tile store, so the jitted step differentiates
    # through the out-of-core path (DESIGN.md C9).  The trainer owns
    # the prepared plan + jitted step so the fault hooks can re-mesh.
    trainer = ElasticGNNTrainer(layers=layers, graph=gn, x=x,
                                y_true=y_true, hidden=hidden,
                                peak_lr=peak_lr, steps=steps,
                                strike_limit=strike_limit)
    gd = trainer.plan
    data = GraphNodeStream(g.num_vertices, classes, batch=batch, seed=1)
    state = {"params": params, "opt": init_opt_state(params)}
    aux = {"layers": layers, "graph": gd, "x": x, "y_true": y_true,
           "num_classes": classes, "trainer": trainer}
    return trainer.step, state, data, gd, aux


def run_gnn(args) -> None:
    """--gnn entry point: fault-tolerant GNN training on the chosen
    aggregation backend (ring = the sharded ring-tiled device mesh;
    graphs over --device-budget train through the streamed out-of-core
    executor automatically — C9).  Shard loss and chronic stragglers
    re-mesh the ring to the survivors and resume from the mesh-agnostic
    checkpoint (C13); `--chaos-seed` replays a deterministic fault
    schedule against the run."""
    import tempfile
    step, state, data, gd, aux = build_gnn(
        model=args.gnn, dataset=args.dataset, backend=args.gnn_backend,
        steps=args.steps, hidden=args.gnn_hidden, batch=args.batch,
        ring_shards=args.gnn_shards,
        device_budget_bytes=args.device_budget or None,
        strike_limit=args.straggler_strikes)
    trainer = aux["trainer"]
    # PreparedPlan (C12): typed plan attributes replace the historical
    # key-probing of ring_meta/tiled_meta/blocks_meta
    shown = {k: v for k, v in gd.meta.items() if k not in ("mesh", "stats")}
    print(f"gnn={args.gnn} backend={gd.backend} "
          f"format={gd.tile_format} footprint={gd.footprint_bytes} "
          f"meta={shown}", flush=True)

    losses = []

    def logged(ps, opt, batch):
        ps, opt, m = step(ps, opt, batch)
        losses.append(float(m["loss"]))
        if len(losses) % 20 == 0:
            print(f"step {len(losses):4d}  loss {losses[-1]:.4f}",
                  flush=True)
        return ps, opt, m

    ckdir = args.ckpt_dir or tempfile.mkdtemp(prefix="engn_gnn_ckpt_")
    mgr = CheckpointManager(ckdir, keep=2, async_save=True)
    step_fn, ckpt, clock_kw, injector = logged, mgr, {}, None
    if args.chaos_seed is not None:
        # deterministic fault schedule on a virtual clock (C13): shard
        # loss, a transient blip, a straggler episode, a torn save
        from repro.distributed.chaos import (ChaosInjector, FaultPlan,
                                             VirtualClock)
        clock = VirtualClock()
        plan = FaultPlan.sample(args.chaos_seed, args.steps)
        injector = ChaosInjector(plan, clock=clock)
        step_fn = injector.wrap_step(logged)
        ckpt = injector.wrap_checkpoint(mgr)
        clock_kw = {"clock": clock, "sleep": clock.sleep}
        print(f"chaos: {injector.describe()}", flush=True)
    runner = FaultTolerantRunner(step_fn, ckpt,
                                 FaultConfig(ckpt_every=args.ckpt_every),
                                 on_failure=trainer.on_failure,
                                 on_straggler=trainer.on_straggler,
                                 **clock_kw)
    start = 0
    if mgr.latest_step() is not None:
        state, meta_d, start = mgr.restore(state)
        data.seek(meta_d.get("cursor", start))
        print(f"restored from step {start}")
    state, last = runner.run(state, data, num_steps=args.steps,
                             start_step=start)
    mgr.wait()
    traj = (f"loss {losses[0]:.3f} -> {losses[-1]:.3f}" if losses
            else "no steps run (checkpoint already at --steps)")
    recov = (f", remesh={trainer.stats['remesh_count']} "
             f"lost_steps={runner.stats['lost_steps']:.0f} "
             f"mttr={runner.stats['mttr_s']:.2f}s"
             if runner.stats["failures"] else "")
    print(f"done: {last} steps, {traj}, saves={runner.stats['saves']}"
          f"{recov}")
    if injector is not None:
        print(f"chaos fired: {injector.stats}", flush=True)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS,
                    help="transformer architecture (LM mode)")
    ap.add_argument("--gnn", choices=["gcn", "gs_pool", "rgcn",
                                      "gated_gcn", "grn"],
                    help="GNN mode: train an EnGN stack instead of an LM")
    ap.add_argument("--gnn-backend", default="segment",
                    choices=["segment", "blocked", "fused", "ring",
                             "tiled"])
    ap.add_argument("--gnn-shards", type=int, default=None,
                    help="ring backend: devices in the ring (default all)")
    ap.add_argument("--gnn-hidden", type=int, default=32)
    ap.add_argument("--dataset", default="pubmed")
    ap.add_argument("--device-budget", type=int, default=0,
                    help="per-shard device budget in bytes (0 = off)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 4 (LM mode) / 256 (GNN mode)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro-steps", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="replay a seeded fault schedule (GNN mode): "
                         "shard loss, transient, straggler, torn save")
    ap.add_argument("--straggler-strikes", type=int, default=3,
                    help="straggler episodes before the ring sheds the "
                         "slow shard (GNN mode)")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    if args.gnn:
        args.batch = args.batch if args.batch is not None else 256
        return run_gnn(args)
    if not args.arch:
        ap.error("one of --arch or --gnn is required")
    args.batch = args.batch if args.batch is not None else 4

    mesh, step, state, data, cfg = build(
        args.arch, smoke=args.smoke, batch=args.batch, seq=args.seq,
        steps=args.steps, micro_steps=args.micro_steps, peak_lr=args.lr,
        q_chunk=min(512, args.seq), loss_chunk=min(256, args.seq))
    print(f"arch={cfg.name} params={T.param_count(cfg)/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    losses = []
    t_last = [time.monotonic()]

    def logged(params, opt, batch):
        with mesh:
            b = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
        now = time.monotonic()
        if len(losses) % 10 == 0:
            print(f"step {len(losses):5d}  loss {losses[-1]:.4f}  "
                  f"{(now - t_last[0]) / 10:.2f}s/step", flush=True)
            t_last[0] = now
        return params, opt, m

    import tempfile
    ckdir = args.ckpt_dir or tempfile.mkdtemp(prefix="engn_ckpt_")
    mgr = CheckpointManager(ckdir, keep=3, async_save=True)
    runner = FaultTolerantRunner(
        logged, mgr, FaultConfig(ckpt_every=args.ckpt_every),
        on_straggler=lambda s, dt: print(f"[straggler] step {s}: {dt:.2f}s",
                                         flush=True))
    start = 0
    if mgr.latest_step() is not None:       # elastic / crash restart
        state, meta, start = mgr.restore(state)
        data.seek(meta.get("cursor", start))
        print(f"restored from step {start}")

    state, last = runner.run(state, data, num_steps=args.steps,
                             start_step=start)
    mgr.wait()
    print(f"done: {last} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"saves={runner.stats['saves']} "
          f"stragglers={runner.stats['stragglers']}")


if __name__ == "__main__":
    main()
