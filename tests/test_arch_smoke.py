"""Per-architecture smoke tests: every assigned arch instantiates a
REDUCED config of the same family and runs one forward/train step and
(where applicable) prefill + decode on CPU, asserting shapes + finiteness.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.launch import specs as SP
from repro.nn import transformer as T
from repro.nn.train_step import make_train_step
from repro.training.optimizer import init_opt_state

B, S = 2, 16


def _batch(cfg, key=0):
    rng = np.random.default_rng(key)
    b = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
    }
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patches, cfg.d_model)),
            jnp.bfloat16)
    if cfg.family == "encdec":
        extras["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16)
    if extras:
        b["extras"] = extras
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    params = T.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, q_chunk=8, loss_chunk=8))
    batch = _batch(cfg)
    params, opt, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    assert loss > 0
    # one more step must change the loss (optimizer actually applied)
    _, _, m2 = step(params, opt, batch)
    assert np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) != loss


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke(arch)
    params = T.init_params(cfg, jax.random.key(1))
    batch = _batch(cfg, key=1)
    max_len = S + 4
    logits, state = jax.jit(
        lambda p, t, e: T.prefill(cfg, p, t, e, max_len=max_len)
    )(params, batch["tokens"], batch.get("extras"))
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert int(state["pos"]) == S

    decode = jax.jit(lambda p, s, t: T.decode_step(cfg, p, s, t))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None] % cfg.vocab_size
    for i in range(3):
        logits, state = decode(params, state, tok)
        assert logits.shape == (B, cfg.padded_vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None] % cfg.vocab_size
    assert int(state["pos"]) == S + 3


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL config must carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expect = {
        "internlm2_20b": (48, 6144, 48, 8, 16384, 92544),
        "minicpm_2b": (40, 2304, 36, 36, 5760, 122753),
        "granite_3_2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen2_72b": (80, 8192, 64, 8, 29568, 152064),
        "llama4_scout_17b_a16e": (48, 5120, 40, 8, 8192, 202048),
        "moonshot_v1_16b_a3b": (48, 2048, 16, 16, 1408, 163840),
        "jamba_1_5_large_398b": (72, 8192, 64, 8, 24576, 65536),
        "llama_3_2_vision_11b": (40, 4096, 32, 8, 14336, 128256),
        "falcon_mamba_7b": (64, 4096, 0, 0, 0, 65024),
        "seamless_m4t_large_v2": (24, 1024, 16, 16, 8192, 256206),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect, (got, expect)


def test_moe_configs():
    l4 = get_config("llama4_scout_17b_a16e")
    assert (l4.n_experts, l4.top_k) == (16, 1)
    ms = get_config("moonshot_v1_16b_a3b")
    assert (ms.n_experts, ms.top_k) == (64, 6)
    jb = get_config("jamba_1_5_large_398b")
    assert (jb.n_experts, jb.top_k) == (16, 2)
    assert jb.attn_every == 8          # 1:7 attention:mamba interleave
    assert jb.subquadratic


def test_param_counts_plausible():
    """Sanity: parameter totals are in the right ballpark for the names."""
    def count(arch):
        return T.param_count(get_config(arch))
    assert 15e9 < count("internlm2_20b") < 25e9
    assert 2e9 < count("minicpm_2b") < 4e9
    assert 60e9 < count("qwen2_72b") < 85e9
    assert 6e9 < count("falcon_mamba_7b") < 9e9
    assert 250e9 < count("jamba_1_5_large_398b") < 500e9
    assert 90e9 < count("llama4_scout_17b_a16e") < 130e9


def test_shape_applicability():
    """long_500k runs only on sub-quadratic archs; dense archs skip."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        ok, why = SP.shape_applicable(cfg, "long_500k")
        assert ok == cfg.subquadratic
        ok4, _ = SP.shape_applicable(cfg, "train_4k")
        assert ok4


def test_smoke_decode_matches_prefill_suffix():
    """Decode must be consistent with prefill: running prefill on k+1
    tokens equals prefill(k) + decode(token k+1) for the logits."""
    cfg = get_smoke("granite_3_2b")
    params = T.init_params(cfg, jax.random.key(2))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    lg_full, _ = T.prefill(cfg, params, toks, max_len=8)
    lg_pre, state = T.prefill(cfg, params, toks[:, :7], max_len=8)
    lg_dec, _ = T.decode_step(cfg, params, state, toks[:, 7:8])
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(lg_full),
                               rtol=3e-2, atol=3e-2)
