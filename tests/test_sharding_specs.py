"""Sharding-spec construction: divisibility fallbacks, logical-axis rules,
dry-run input specs.  Single-device meshes (no forced device count here —
smoke tests must see 1 device; the real meshes are exercised by the
dry-run deliverable)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke
from repro.distributed.sharding import batch_pspec
from repro.launch import specs as SP
from repro.launch.mesh import single_device_mesh
from repro.nn.param import ParamSpec, spec_to_pspec
from repro.nn.sharding import Constrainer, make_rules, param_pspecs


def test_spec_to_pspec_divisibility_fallback():
    ms = {"data": 16, "model": 16}
    # divides: sharded
    s = ParamSpec((256, 64), ("embed", "mlp"))
    assert spec_to_pspec(s, ms) == P("data", "model")
    # does not divide: replicated on that dim
    s2 = ParamSpec((100, 64), ("embed", "mlp"))
    assert spec_to_pspec(s2, ms) == P(None, "model")
    # logical axis missing from rules: replicated
    s3 = ParamSpec((256,), (None,))
    assert spec_to_pspec(s3, ms) == P(None)


class _FakeMesh:
    """Duck-typed mesh: batch_pspec only reads axis_names/devices.shape."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.zeros(shape)


def test_batch_pspec_shape_fallback():
    """The long_500k regression: batch=1 must not shard over data=16."""
    mesh = _FakeMesh((16, 16), ("data", "model"))
    rules = {"batch": "data", "seq": "model"}
    assert batch_pspec(mesh, 2, rules=rules, shape=(1, 1)) == P(None, None)
    sp = batch_pspec(mesh, 2, seq_axis=1, rules=rules, shape=(128, 32768))
    assert sp == P("data", "model")
    # batch divides but seq does not
    sp2 = batch_pspec(mesh, 2, seq_axis=1, rules=rules, shape=(128, 100))
    assert sp2 == P("data", None)


def test_constrainer_replicates_non_dividing():
    mesh = single_device_mesh()
    sc = Constrainer(mesh)
    x = jnp.zeros((3, 5))
    y = sc(x, ("batch", "seq"))           # 1x1 mesh: all divides
    assert y.shape == x.shape


def test_make_rules_drops_missing_axes():
    mesh = single_device_mesh()           # axes: data, model
    rules = make_rules(mesh)
    assert rules["batch"] == ("data",)    # "pod" dropped
    assert rules["embed"] == "data"
    rules_ns = make_rules(mesh, seq_sharded=False)
    assert rules_ns["seq"] is None


@pytest.mark.parametrize("arch", ["granite_3_2b", "jamba_1_5_large_398b",
                                  "seamless_m4t_large_v2"])
def test_param_pspecs_tree_matches_params(arch):
    cfg = get_smoke(arch)
    mesh = single_device_mesh()
    ps = param_pspecs(cfg, mesh)
    from repro.nn import transformer as T
    ab = T.abstract_params(cfg)
    # same tree structure
    assert jax.tree.structure(ps) == jax.tree.structure(
        jax.tree.map(lambda x: 0, ab))


def test_train_batch_specs_shapes():
    cfg = get_config("granite_3_2b")
    b = SP.train_batch_specs(cfg, 4096, 256)
    assert b["tokens"].shape == (256, 4096)
    assert b["labels"].dtype == jnp.int32

    vlm = get_config("llama_3_2_vision_11b")
    bv = SP.train_batch_specs(vlm, 128, 4)
    assert "image_embeds" in bv["extras"]
    assert bv["extras"]["image_embeds"].shape[0] == 4

    ed = get_config("seamless_m4t_large_v2")
    be = SP.train_batch_specs(ed, 128, 4)
    assert be["extras"]["frames"].shape == (4, 128, ed.d_model)


def test_decode_state_specs_cover_families():
    for arch, keys in [("granite_3_2b", {"k", "v"}),
                       ("falcon_mamba_7b", {"conv", "ssm"}),
                       ("jamba_1_5_large_398b", {"k", "v", "conv", "ssm"}),
                       ("llama_3_2_vision_11b", {"k", "v", "mk", "mv"})]:
        cfg = get_config(arch)
        st = SP.decode_state_specs(cfg, 4, 64)
        leaf_names = set()
        for slot in st["layers"].values():
            leaf_names |= set(slot.keys())
        assert keys <= leaf_names, (arch, leaf_names)


def test_decode_state_pspecs_no_crash():
    mesh = single_device_mesh()
    for arch in ("granite_3_2b", "falcon_mamba_7b"):
        cfg = get_config(arch)
        st = SP.decode_state_specs(cfg, 4, 64)
        ps = SP.decode_state_pspecs(cfg, st, mesh)
        assert jax.tree.structure(ps, is_leaf=lambda x: isinstance(x, P))


def test_elastic_mesh_always_valid():
    for n in (1, 2, 3, 6, 16):
        # can't make more devices than exist; just exercise the divisor math
        mp = 16
        m = min(mp, n)
        while n % m:
            m //= 2
        assert n % max(m, 1) == 0
