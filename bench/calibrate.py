#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates; never run by the
benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--seconds 5] [--rates 10,20,40]

Training cells: for each of `--seeds`, the program's first three steps
against the reference (the lower readings of each compared number);
for each of `--control-seeds`, the same numbers for the control (the
reference one step of precision below the workload's, put in the
program's place) and for the planted half-batch fault (the reference's
loss over half the batch).

Serving cells: for each of `--seeds`, a window of `--seconds` at the
cell's rate, and the row gaps of the program and of the control on
the rows it answered.  With `--rates`, one set-up serves a window at
each rate instead, and the latencies show where the backlog starts to
grow (the knee).

One JSON line per reading goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

harness.add_program_to_path()

def emit(**kw):
    print(json.dumps(kw, default=float), flush=True)


def calibrate_train(c, args):
    from bench.jobs import train
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = train.setup(c, seed)
        got = (ctx["losses"], ctx["m1"], ctx["p3"])
        train.release(ctx)
        want = train.reference_run(ctx)
        emit(kind="program", seed=seed, backend=ctx["backend"],
             tile_format=ctx["tile_format"], losses=got[0],
             ref_losses=want[0], s=time.perf_counter() - t,
             **train.compare(ctx, got, want))
    for seed in args.control_seeds:
        ctx = train.inputs_only(c, seed)
        want = train.reference_run(ctx)
        for kind, kw in (("control", {"control": True}),
                         ("half_batch", {"half_batch": True})):
            got = train.reference_run(ctx, **kw)
            emit(kind=kind, seed=seed, **train.compare(ctx, got, want))


def serve_summary(rec, seconds):
    """A window's latencies by quarter of the window (p50 and p95 of the
    requests due in each) and the backlog, the requests due but not yet
    answered, at each tenth of it: a backlog that keeps growing over
    the window is a rate above the knee."""
    import numpy as np
    from bench.metrics._latency import percentile_ms
    due, lat = rec["due_s"], rec["latency_s"]
    quarters = np.minimum((4 * due / seconds).astype(int), 3)
    by_quarter = [np.sort(lat[quarters == k]) for k in range(4)]

    def pct(a, q):
        return (float(a[int(np.ceil(q * a.size)) - 1]) * 1e3
                if a.size else None)
    backlog = [int(np.sum((due <= t) & (due + lat > t)))
               for t in np.linspace(0.1, 1.0, 10) * seconds]
    return {"attempted": rec["attempted"], "failed": rec["failed"],
            "p50_ms": percentile_ms(rec, 0.5),
            "p95_ms": percentile_ms(rec, 0.95),
            "quarter_p50_ms": [pct(a, 0.5) for a in by_quarter],
            "quarter_p95_ms": [pct(a, 0.95) for a in by_quarter],
            "backlog": backlog,
            "answered_in_window": int(np.sum(due + lat <= seconds)),
            "drain_s": rec["drain_s"],
            "open_at_close": rec["open_at_close"],
            "late_max_s": float(np.max(rec["late_s"], initial=0.0)),
            "hit_rate": (rec["cache"].get("hits", 0)
                         / max(rec["cache"].get("hits", 0)
                               + rec["cache"].get("misses", 0), 1)),
            "subgraphs": rec["engine"]["subgraphs"],
            "engine_compiles": rec["engine_compiles"]}


def calibrate_serve(c, args):
    from bench.jobs import serve
    if args.rates:
        ctx = serve.setup(c, args.seeds[0])
        for rate in args.rates:
            ctx["wl"]["traffic"]["rate"] = rate
            ctx["engine"].reset_telemetry()
            ctx["pipe"].reset_telemetry()
            rec = serve.window(ctx, args.seconds)
            emit(kind="sweep", rate=rate, seed=args.seeds[0],
                 **serve_summary(rec, args.seconds))
        return
    for seed in args.seeds:
        ctx = serve.setup(c, seed)
        rec = serve.window(ctx, args.seconds)
        serve.release(ctx)
        want = serve.reference_logits(ctx)
        ctl = serve.reference_logits(ctx, control=True)
        rows = [(ids, ctl[ids]) for ids, _ in ctx["answered"]]
        emit(kind="program", seed=seed,
             row_gap=serve.row_gap(ctx["answered"], want),
             control_row_gap=serve.row_gap(rows, want),
             **serve_summary(rec, args.seconds))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(v) for v in s.split(",")])
    ap.add_argument("--control-seeds", default=[],
                    type=lambda s: [int(v) for v in s.split(",") if v])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default=[],
                    type=lambda s: [float(v) for v in s.split(",") if v])
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    try:
        harness.chips(c["entry"]["chips"])
    except harness.NoChip as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    job = c["workload"]["job"]
    {"train": calibrate_train, "serve": calibrate_serve}[job](c, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
