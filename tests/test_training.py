"""Training substrate: optimizer, schedules, grad accumulation,
gradient compression (error feedback), GNN end-to-end loss descent."""
import numpy as np
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # clean checkout: vendored fallback
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_smoke
from repro.core.models import make_gnn_stack, init_stack, apply_stack
from repro.core.engn import prepare_graph
from repro.distributed.compression import (compression_ratio,
                                           dequantize_int8,
                                           make_error_feedback_transform,
                                           quantize_int8)
from repro.graphs.generate import rmat_graph, random_features
from repro.nn import transformer as T
from repro.nn.train_step import make_grad_accum_train_step, make_train_step
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      clip_by_global_norm, global_norm,
                                      init_opt_state)
from repro.training.schedule import cosine_schedule, wsd_schedule


# ---------------------------------------------------------------- optimizer
def test_adamw_reduces_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    opt = init_opt_state(params)
    cfg = AdamWConfig(weight_decay=0.0)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt = adamw_update(cfg, g, opt, params, 0.05)
    assert float(loss(params)) < 1e-2


def test_weight_decay_only_on_matrices():
    cfg = AdamWConfig(weight_decay=0.5)
    params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    zeros = jax.tree.map(jnp.zeros_like, params)
    opt = init_opt_state(params)
    p2, _ = adamw_update(cfg, zeros, opt, params, 0.1)
    assert float(p2["w"][0, 0]) < 1.0     # decayed
    assert float(p2["b"][0]) == 1.0       # not decayed


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}    # norm 5
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    # below the limit: untouched
    same, _ = clip_by_global_norm(g, 10.0)
    np.testing.assert_allclose(np.asarray(same["a"]), [3.0, 4.0])


# ---------------------------------------------------------------- schedules
def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(s, peak_lr=1.0, warmup=10, total=100))
           for s in range(101)]
    assert lrs[0] == 0.0
    np.testing.assert_allclose(lrs[10], 1.0, rtol=1e-5)
    assert lrs[100] < 0.2
    assert all(a <= b + 1e-9 for a, b in zip(lrs[:10], lrs[1:11]))  # warmup up


def test_wsd_schedule_stable_phase():
    lrs = [float(wsd_schedule(s, peak_lr=1.0, warmup=10, total=100))
           for s in range(101)]
    np.testing.assert_allclose(lrs[20], 1.0, rtol=1e-6)   # stable
    np.testing.assert_allclose(lrs[80], 1.0, rtol=1e-6)   # still stable
    assert lrs[100] < 0.05                                # decayed


# ------------------------------------------------------------- grad accum
def test_grad_accum_matches_full_batch():
    cfg = get_smoke("granite_3_2b")
    params = T.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
    }
    step_full = make_train_step(cfg, q_chunk=8, loss_chunk=8)
    step_acc = make_grad_accum_train_step(cfg, micro_steps=2, q_chunk=8,
                                          loss_chunk=8)
    opt = init_opt_state(params)
    p1, _, m1 = jax.jit(step_full)(params, opt, batch)
    p2, _, m2 = jax.jit(step_acc)(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    l1, l2 = jax.tree.leaves(p1), jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-5)


def test_lm_loss_decreases():
    """A few hundred steps on a tiny LM must reduce loss on a fixed batch."""
    cfg = get_smoke("minicpm_2b")
    params = T.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
    }
    step = jax.jit(make_train_step(cfg, peak_lr=3e-3, warmup=5,
                                   total_steps=60, q_chunk=8, loss_chunk=8))
    opt = init_opt_state(params)
    losses = []
    for _ in range(40):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::8]


# ------------------------------------------------------------- compression
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 100))
def test_int8_quantization_bounded_error(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(256).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6   # round-to-nearest bound


def test_error_feedback_accumulates_to_truth():
    """Sum of compressed gradients + final residual == sum of raw
    gradients: error feedback loses nothing over time."""
    transform, init_error = make_error_feedback_transform()
    rng = np.random.default_rng(0)
    grads = [{"w": jnp.asarray(rng.standard_normal(64).astype(np.float32))}
             for _ in range(20)]
    err = init_error(grads[0])
    total_comp = jnp.zeros(64)
    for g in grads:
        cg, err = transform(g, err)
        total_comp = total_comp + cg["w"]
    total_raw = sum(g["w"] for g in grads)
    np.testing.assert_allclose(np.asarray(total_comp + err["w"]),
                               np.asarray(total_raw), rtol=1e-4, atol=1e-4)


def test_compression_ratio_about_quarter():
    params = {"w": jnp.zeros((1024, 1024))}
    assert abs(compression_ratio(params) - 0.25) < 0.01


def test_train_step_with_compression_still_learns():
    cfg = get_smoke("granite_3_2b")
    params = T.init_params(cfg, jax.random.key(2))
    transform, init_error = make_error_feedback_transform()
    err = [init_error(params)]

    def grad_transform(grads):
        cg, err[0] = transform(grads, err[0])
        return cg

    step = make_train_step(cfg, peak_lr=3e-3, warmup=5, total_steps=50,
                           q_chunk=8, loss_chunk=8,
                           grad_transform=grad_transform)
    rng = np.random.default_rng(2)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                              jnp.int32),
    }
    opt = init_opt_state(params)
    losses = []
    for _ in range(25):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


# ------------------------------------------------------------- GNN training
def test_gnn_node_classification_learns():
    """End-to-end GNN training on the EnGN processing model."""
    g = rmat_graph(120, 900, seed=0).gcn_normalized()
    f, h, classes = 16, 32, 4
    layers = make_gnn_stack("gcn", [f, h, classes])
    params = init_stack(layers, jax.random.key(3))
    gd = prepare_graph(g, layers[0].cfg)
    x = jnp.asarray(random_features(g.num_vertices, f, seed=1))
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.integers(0, classes, g.num_vertices), jnp.int32)

    def loss_fn(ps):
        logits = apply_stack(layers, ps, gd, x)
        ll = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(ll, y[:, None], 1))

    opt = init_opt_state(params)
    cfg = AdamWConfig(weight_decay=0.0)
    step = jax.jit(lambda ps, o: (lambda lv, g: adamw_update(cfg, g, o, ps, 0.01) + (lv,))(*jax.value_and_grad(loss_fn)(ps)))
    l0 = float(loss_fn(params))
    for _ in range(150):
        params, opt, _ = step(params, opt)
    l1 = float(loss_fn(params))
    assert l1 < l0 - 0.3, (l0, l1)
