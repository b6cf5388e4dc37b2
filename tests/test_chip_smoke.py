"""`chip_smoke.py` off the chip: its phases at a tiny size on the CPU,
its refusal to report without a TPU, and the compile-cache helper its
entry points share."""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def prob(smoke):
    return smoke.make_problem(max_vertices=400, max_edges=3000,
                              feature_dim=48)


def _records(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_oracle_matches_segment_forward(smoke, prob, capsys):
    y = smoke.forward_resident(prob, "segment")
    assert y.shape == (prob.graph.num_vertices, prob.classes)
    (rec,) = _records(capsys)
    assert rec["backend"] == "segment" and rec["rel_err"] < 1e-5


def test_phase_forward_covers_every_backend(smoke, prob, capsys):
    smoke.phase_forward(prob)
    recs = [r for r in _records(capsys) if "rel_err" in r]
    got = {(r["backend"], r["tile_format"], r.get("stage_order"))
           for r in recs}
    assert got == {("segment", None, None), ("blocked", "dense", None),
                   ("blocked", "packed", None), ("tiled", "packed", "afu"),
                   ("tiled", "packed", "fau")}
    assert all(r["rel_err"] < 1e-5 for r in recs)


def test_phase_training_gradients_agree(smoke, prob, capsys):
    smoke.phase_training(prob)
    recs = _records(capsys)
    (cmp,) = [r for r in recs if "compare" in r]
    assert cmp["grad_rel_err"] < 1e-5
    assert {r["backend"] for r in recs if "losses" in r} == {"segment",
                                                               "blocked"}


def test_phase_serving_answers_every_request(smoke, prob, capsys):
    smoke.phase_serving(prob)
    (rec,) = _records(capsys)
    assert rec["statuses"] == ["ok"]
    assert rec["responses"] == smoke.REQUESTS


def test_phase_ring_on_four_host_devices(smoke, prob, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    smoke.phase_ring(prob)
    (ring,) = [r for r in _records(capsys) if r.get("phase") == "ring"]
    assert len(ring["stripe_devices"]) == 4
    assert ring["collective_permutes"] > 0


@pytest.mark.parametrize("alone", [False, True])
def test_exits_nonzero_without_tpu_or_repo(tmp_path, alone):
    """On the CPU, or copied away from the repository, the script fails
    and prints no result line."""
    script = ROOT / "chip_smoke.py"
    cwd = ROOT
    if alone:
        (tmp_path / "chip_smoke.py").write_text(script.read_text())
        script, cwd = tmp_path / "chip_smoke.py", tmp_path
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_prefers_the_environment(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # the variable is JAX's own: the helper sets nothing beside it
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
