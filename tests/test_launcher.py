"""The production launcher assembles and runs for every family."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.launch.train import build


@pytest.mark.parametrize("arch", ["granite_3_2b", "moonshot_v1_16b_a3b",
                                  "falcon_mamba_7b"])
def test_launcher_build_and_step(arch):
    mesh, step, state, data, cfg = build(arch, smoke=True, batch=2,
                                         seq=16, steps=5, q_chunk=8,
                                         loss_chunk=8)
    with mesh:
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, m = step(state["params"], state["opt"], batch)
    assert np.isfinite(float(m["loss"]))
    assert int(opt["count"]) == 1


def test_launcher_grad_accum_path():
    mesh, step, state, data, cfg = build("granite_3_2b", smoke=True,
                                         batch=4, seq=16, steps=5,
                                         micro_steps=2, q_chunk=8,
                                         loss_chunk=8)
    with mesh:
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        _, _, m = step(state["params"], state["opt"], batch)
    assert np.isfinite(float(m["loss"]))


def _gnn_losses(backend, steps=8, **kw):
    from repro.launch.train import build_gnn
    step, state, data, gd, aux = build_gnn(
        model="gcn", dataset="pubmed", backend=backend, steps=steps,
        hidden=8, batch=64, max_vertices=300, max_edges=2000,
        feature_dim=128, **kw)
    losses = []
    ps, opt = state["params"], state["opt"]
    for _ in range(steps):
        ps, opt, m = step(ps, opt, next(data))
        losses.append(float(m["loss"]))
    return losses, gd


def test_launcher_gnn_mode_trains_on_ring_backend():
    """--gnn mode: the sharded ring-tiled backend trains (gradients flow
    through the ppermute rotation) and takes the same optimisation
    trajectory as the segment reference."""
    seg_losses, _ = _gnn_losses("segment")
    ring_losses, gd = _gnn_losses("ring", ring_shards=1)
    assert gd.backend == "ring"
    assert all(np.isfinite(ring_losses))
    assert ring_losses[-1] < ring_losses[0]
    np.testing.assert_allclose(ring_losses, seg_losses,
                               rtol=1e-3, atol=1e-4)


def test_launcher_gnn_mode_budget_spill_trains_streamed():
    """A per-shard budget too small for the ring stripe spills to the
    streamed tiled executor — which now trains (C9: the streamed
    aggregate carries a custom_vjp whose backward re-streams the
    transposed tile store), following the segment trajectory instead
    of refusing at build time."""
    seg_losses, _ = _gnn_losses("segment", steps=3)
    spill_losses, gd = _gnn_losses("ring", steps=3, ring_shards=1,
                                   device_budget_bytes=50_000)
    assert gd.backend == "tiled"
    assert gd.meta["trainable"] is True
    assert all(np.isfinite(spill_losses))
    np.testing.assert_allclose(spill_losses, seg_losses,
                               rtol=1e-3, atol=1e-4)
    assert gd.carrier["tiled_exec"].stats.bwd_tiles > 0
