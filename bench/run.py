#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name: its entry in BENCHMARK.json,
`bench/workloads/<cell>.json` (job, backend, traffic, limits),
`bench/configs/<config>.json` (model, widths, graph), the job module
`bench/jobs/<job>.py`, and one reader `bench/metrics/<metric>.py` per
metric.  With `--trace 0` the run reports the cell's end-to-end metrics;
with `--trace 1` it takes a profiler trace of the window and reports the
per-layer metrics, with the device's busy time and a breakdown.

The run fails, printing no result, unless JAX finds as many TPU chips as
the cell asks for.  The last line of standard output is the result; the
numbers that decide `correct`, each beside its limit, close it and are
also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import cost, harness  # noqa: E402

harness.add_program_to_path()


def metric_names(bench: Dict, cell_name: str, trace: bool) -> List[str]:
    """The metrics a cell reports: those that list it, and those that
    list no cells."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m["name"] for m in group
            if cell_name in m.get("workloads", [cell_name])]


def result(cell_name: str, seed: int, seconds: float, trace: bool,
           devices: Optional[list] = None, t_start: float = T_START,
           c: Optional[Dict] = None) -> Dict:
    """Run one cell and return its result object (the last line).
    Tests pass `devices` (to run without a chip) and `c`, the cell's
    files, at a size a CPU can hold."""
    bench = harness.benchmark()
    c = c or harness.cell(cell_name)
    if devices is None:
        devices = harness.chips(c["entry"]["chips"])
        harness.enable_compile_cache()
    job = importlib.import_module(f"bench.jobs.{c['workload']['job']}")
    ctx = job.setup(c, seed)
    counter = harness.CompileCounter.get()
    tracer = harness.Trace(devices) if trace else None
    with counter:
        if tracer is not None:
            with tracer:
                rec = job.window(ctx, seconds)
        else:
            rec = job.window(ctx, seconds)
    kind = devices[0].device_kind
    rec.update(setup_s=rec["t0"] - t_start, prepare_s=ctx["prepare_s"],
               memory_peak_bytes=harness.memory_peak(devices),
               chips=len(devices), job=c["workload"]["job"],
               trace=tracer.reduced if tracer else None,
               peak=cost.peaks(kind) if devices[0].platform == "tpu"
               else None)
    numbers = job.verify(ctx)
    limits = c["workload"]["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = (rec["failed"] == 0
               and all(ch["value"] <= ch["limit"] for ch in checks.values()))
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    for name in metric_names(bench, cell_name, trace):
        value = harness.module("metrics", name).read(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics,
           "device": device}
    if tracer is not None:
        device.update(busy_s=tracer.reduced["busy_s"],
                      window_s=tracer.reduced["window_s"])
        out["breakdown"] = {"device_ops": tracer.reduced["device_ops"],
                            "idle_gaps": tracer.reduced["idle_gaps"]}
    out["checks"] = checks
    out["_info"] = info(rec, counter)
    return out


def info(rec: Dict, counter) -> Dict:
    """What the run prints on an earlier line: compiles in the window,
    and for served traffic how late the load generator sent."""
    import numpy as np
    out = {"compiles_in_window": counter.compiles,
           "traces_in_window": counter.traces}
    for key in ("steps", "backend", "tile_format", "engine_compiles",
                "drain_s", "final_loss", "in_flight", "setup_parts"):
        if key in rec:
            out[key] = rec[key]
    if "late_s" in rec and len(rec["late_s"]):
        late = np.sort(rec["late_s"])
        out["generator_late_p95_s"] = float(
            late[int(np.ceil(0.95 * late.size)) - 1])
        out["generator_late_max_s"] = float(late[-1])
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = result(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"info": out.pop("_info")}, default=float), flush=True)
    for name, ch in out["checks"].items():
        print(f"check {name} {ch['value']!r} limit {ch['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
