"""BENCHMARK.json and every file it names, loaded by name: the shapes
and characters the benchmark file allows, the metric readers, and the
command's refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1:] == ["bench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert one_line(entry["source"]) and one_line(entry["why"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg
        assert not key.endswith(("_dim", "_rank")), key
    assert len(cfg["source"]) <= 200
    assert cfg["dims"] == [cfg["feature_dim"], cfg["hidden_dim"],
                           cfg["num_labels"]]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
    c = harness.cell(name)
    entry, wl = c["entry"], c["workload"]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
    assert one_line(entry["why"])
    assert wl["name"] == name and wl["config"] == entry["config"]
    assert (harness.BENCH / "jobs" / f"{wl['job']}.py").exists()
    assert wl["limits"] and all(v > 0 for v in wl["limits"].values())
    reported = {m["name"] for m in METRICS
                if name in m.get("workloads", [name])}
    assert "setup_s" in reported
    assert len(reported & {m["name"] for m in BENCH["end_to_end"]}) >= 2
    assert reported & {m["name"] for m in BENCH["per_layer"]}


def test_cells_are_unique_pairs():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    e2e = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if e2e else {"layer", "moves"}
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (SOURCES_E2E if e2e else SOURCES)
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    reader = harness.module("metrics", metric["name"])
    assert reader.read({}) is None
    if e2e:
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert one_line(metric["layer"])
    moves = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    for cell in metric.get("workloads", CELLS):
        assert cell in moves.get("workloads", [cell])
    if metric["name"].endswith("_roofline") or "_roofline." in metric[
            "name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_metric_is_there():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names and len(names) == len(set(names))
    assert len({m["name"] for m in METRICS}) == len(METRICS)


def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.ROOT / BENCH["command"][1]),
         "--workload", CELLS[0], "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
