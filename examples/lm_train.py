"""Train a ~100M-parameter LM (scaled granite family) for a few hundred
steps with the full distributed-training substrate: sharded train step,
grad accumulation, WSD/cosine schedule, checkpointing.

    PYTHONPATH=src python examples/lm_train.py [--steps 200]

On this CPU container the mesh is 1x1; on a pod the same code runs under
make_production_mesh() with the identical sharding rules (see
src/repro/launch/dryrun.py for the 256/512-chip lowering proof).
"""
import argparse
import tempfile

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticTokenStream
from repro.distributed.fault import FaultConfig, FaultTolerantRunner
from repro.launch.mesh import single_device_mesh
from repro.nn.config import ModelConfig
from repro.nn import transformer as T
from repro.nn.sharding import Constrainer
from repro.nn.train_step import make_train_step
from repro.training.optimizer import init_opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    # ~100M params: 8 layers, d=512, vocab 32k
    cfg = ModelConfig(name="lm-100m", family="dense", num_layers=8,
                      d_model=512, n_heads=8, n_kv_heads=4, d_ff=2048,
                      vocab_size=32_000)
    n = T.param_count(cfg)
    print(f"model: {n/1e6:.1f}M params")

    mesh = single_device_mesh()
    sc = Constrainer(mesh)
    step = jax.jit(make_train_step(cfg, sc=sc, peak_lr=3e-4, warmup=20,
                                   total_steps=args.steps, q_chunk=64,
                                   loss_chunk=64))

    params = T.init_params(cfg, jax.random.key(0))
    data = SyntheticTokenStream(cfg.vocab_size, batch=args.batch,
                                seq=args.seq, seed=0)

    losses = []

    def logged(ps, opt, batch):
        import jax.numpy as jnp
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        ps, opt, m = step(ps, opt, b)
        losses.append(float(m["loss"]))
        if len(losses) % 25 == 0:
            print(f"step {len(losses):4d}  loss {losses[-1]:.4f}")
        return ps, opt, m

    with tempfile.TemporaryDirectory() as ckdir:
        runner = FaultTolerantRunner(logged, CheckpointManager(ckdir),
                                     FaultConfig(ckpt_every=100))
        state = {"params": params, "opt": init_opt_state(params)}
        state, last = runner.run(state, data, num_steps=args.steps)

    print(f"done: {last} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
