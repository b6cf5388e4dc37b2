"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig15] [--smoke]

`--smoke` shrinks every bench to tiny sizes with one repetition — the CI
bench-smoke job runs it and uploads the CSV as an artifact so the perf
trajectory is recorded per PR.

Emits ``name,value,derived`` CSV rows (also saved to
experiments/bench_results.csv), plus a machine-readable ``BENCH_10.json``
summary — per-bench best throughput, the train-step (fwd+bwd) rows,
packed-vs-dense speedups, the serving-pipeline rows, the fault-recovery
rows, the dynamic-graph update rows and the parity gates — so the perf
trajectory can be diffed across PRs without parsing the CSV.
(BENCH_9.json is the committed snapshot of the previous PR's sweep; the
schema is documented in docs/benchmarks.md.)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# Multi-device host view BEFORE any bench module imports jax — the same
# 1-CPU host-callback deadlock workaround tests/conftest.py applies (a
# jitted callback-loop bench on a single-lane XLA:CPU waits forever for
# the core the outer program holds; see README "Tests").  An explicit
# user-provided count is respected.  If jax was already imported with
# an initialised backend the env write is a silent no-op and the
# callback benches would deadlock on one lane — refuse loudly instead.
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG + "=8").strip()
    if "jax" in sys.modules:
        import jax

        if (jax.default_backend() == "cpu"
                and jax.local_device_count() < 8):
            raise RuntimeError(
                f"benchmarks/run.py set XLA_FLAGS {_FLAG}=8 but jax "
                f"had already initialised its backend with "
                f"{jax.local_device_count()} CPU device(s); a 1-lane "
                "XLA:CPU deadlocks in the host-callback benches.  "
                f"Export XLA_FLAGS='{_FLAG}=8' before launching, or "
                "avoid importing jax before benchmarks.run.")

from benchmarks import (bench_stage_breakdown, bench_edge_reorg,
                        bench_dim_sensitivity, bench_dasr, bench_tiling,
                        bench_tiled_exec, bench_davc, bench_scaling,
                        bench_throughput, bench_ablation, bench_serving,
                        bench_ring_tiled, bench_fault, bench_updates)
from benchmarks import common
from benchmarks.common import rows

BENCHES = {
    "fig2": bench_stage_breakdown,      # stage breakdown
    "fig10": bench_throughput,          # throughput vs baseline
    "fig12": bench_edge_reorg,          # edge reorg / utilisation
    "fig13": bench_dim_sensitivity,     # dimension sensitivity
    "fig14": bench_dasr,                # DASR speedup
    "fig15": bench_tiling,              # tiling schedule I/O (model)
    "tiled": bench_tiled_exec,          # out-of-core tiled executor
    "ring_tiled": bench_ring_tiled,     # sharded ring-tiled mesh scaling
    "fig16": bench_davc,                # DAVC hit rates
    "fig17": bench_scaling,             # PE/ring scaling
    "ablation": bench_ablation,         # technique-by-technique
    "serving": bench_serving,           # serving engine req/s + cache
    "fault": bench_fault,               # recovery time + ckpt overhead
    "updates": bench_updates,           # dynamic-graph delta merges
}


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated figure keys (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, one repetition (CI bench-smoke)")
    args = ap.parse_args()
    if args.smoke:
        common.set_smoke(True)
        print("# smoke mode: tiny sizes, 1 repetition", flush=True)
    keys = [k for k in args.only.split(",") if k] or list(BENCHES)

    print("name,value,derived")
    for k in keys:
        t0 = time.time()
        print(f"# --- {k} ({BENCHES[k].__doc__.splitlines()[0].strip()})",
              flush=True)
        BENCHES[k].run()
        print(f"# {k} done in {time.time() - t0:.1f}s", flush=True)

    # smoke rows go to their own file: bench_results.csv is the tracked
    # full-run trajectory and must not be clobbered by partial CI rows
    out = Path("experiments/bench_smoke.csv" if args.smoke
               else "experiments/bench_results.csv")
    out.parent.mkdir(exist_ok=True)
    out.write_text("name,value,derived\n" + "\n".join(rows()) + "\n")
    print(f"# wrote {out}")

    summary = summarize(rows(), smoke=args.smoke)
    Path("BENCH_10.json").write_text(json.dumps(summary, indent=2,
                                                sort_keys=True) + "\n")
    print("# wrote BENCH_10.json")
    return 0


def summarize(csv_rows, smoke: bool) -> dict:
    """Condense the CSV rows into the PR's perf-trajectory point: the
    best throughput per bench, the train-step (fwd+bwd) rows, every
    packed-vs-dense speedup, and the packed parity gates."""
    parsed = []
    for row in csv_rows:
        name, value, derived = row.split(",", 2)
        try:
            parsed.append((name, float(value), derived))
        except ValueError:
            parsed.append((name, value, derived))
    best = {}
    for name, value, _ in parsed:
        if not isinstance(value, float):
            continue
        if name.endswith("edges_per_s") or name.endswith("requests_per_s"):
            bench = name.split("/", 1)[0]
            if value > best.get(bench, {}).get("value", 0.0):
                best[bench] = {"row": name, "value": value}
    return {
        "issue": 10,
        "smoke": smoke,
        "best_throughput": best,
        "train": {n: v for n, v, _ in parsed if "/train_" in n},
        "fault": {n: v for n, v, _ in parsed
                  if n.startswith("fault/") and isinstance(v, float)},
        "packed_vs_dense": {n: v for n, v, _ in parsed
                            if "packed_speedup" in n},
        "queue": {n: v for n, v, _ in parsed
                  if "queue" in n or "quant" in n},
        "serving": {n: v for n, v, _ in parsed
                    if n.startswith("serving/")
                    and isinstance(v, float)},
        "updates": {n: v for n, v, _ in parsed
                    if n.startswith("updates/")
                    and isinstance(v, float)},
        "parity": {n: v for n, v, _ in parsed if "parity" in n},
        "fill_factor": {n: v for n, v, _ in parsed
                        if "fill_factor" in n},
        "autotune": {n: d for n, _, d in parsed if "autotune" in n},
        "rows": len(parsed),
    }


if __name__ == "__main__":
    sys.exit(main())
