"""What every job and metric of the benchmark shares: finding the files
a run names, the chip check, the compile cache, the compile counter,
host spans and the profiler trace."""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
TRACE_DIR = BENCH / ".trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def add_program_to_path() -> None:
    for p in (str(SRC), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return read_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict:
    """The BENCHMARK.json entry, workload file and configuration file
    of a cell, by the cell's name."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return {"entry": entry,
            "workload": read_json(BENCH / "workloads" / f"{name}.json"),
            "config": read_json(BENCH / "configs"
                                / f"{entry['config']}.json")}


def module(kind: str, name: str) -> ModuleType:
    """bench/<kind>/<name>.py, loaded by its file name (metric names
    hold dots, so they are not importable module names)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", f"bench_{kind}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chips(count: int) -> list:
    """The first `count` TPU devices; NoChip without them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        raise NoChip(f"the cell needs {count} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:count]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at `$JAX_COMPILATION_CACHE_DIR`
    when set, else at the checkout's fixed `.jax_cache` (the directory
    is part of the cache key, so it never moves).  Every program is
    cached, however short its compile."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def matmul_precision(workload: Dict) -> None:
    """Run the program at the matmul precision the workload states:
    "default" (a float32 matmul is one bfloat16 pass on a TPU) or
    "high" (three passes)."""
    import jax
    prec = workload.get("matmul_precision", "default")
    if prec not in ("default", "high"):
        raise ValueError(f"unknown matmul_precision {prec!r}")
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)


class CompileCounter:
    """Counts the traces and backend compiles JAX makes while `active`.
    JAX keeps listeners for the life of the process, so one counter is
    registered once and switched on and off."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.active = False
        self.traces = 0
        self.compiles = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            cls._instance = inst = cls()
            jax.monitoring.register_event_duration_secs_listener(
                inst._on_event)
        return cls._instance

    def _on_event(self, name: str, *_args, **_kw) -> None:
        if not self.active:
            return
        if name.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("backend_compile_duration"):
            self.compiles += 1

    def __enter__(self):
        self.traces = self.compiles = 0
        self.active = True
        return self

    def __exit__(self, *exc):
        self.active = False
        return False


def span(name: str):
    """A host span in the profiler's trace (costs next to nothing when
    no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Trace:
    """The profiler over the measured window, reduced to numbers by
    trace_reduce once it stops; the raw trace is deleted then."""

    def __init__(self, devices: list):
        self.devices = devices
        self.reduced: Optional[Dict] = None

    def __enter__(self):
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        return self

    def __exit__(self, *exc):
        import jax
        from bench import trace_reduce
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
                if not files:
                    raise RuntimeError("the profiler wrote no trace")
                self.reduced = trace_reduce.reduce(trace_reduce.load(
                    files[-1], devices=[d.id for d in self.devices]))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return False


def memory_peak(devices: list) -> Optional[int]:
    peaks: List[int] = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
