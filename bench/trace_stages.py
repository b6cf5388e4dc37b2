#!/usr/bin/env python3
"""The program's own spans and stage scopes in a profiler trace.

`bench/trace_reduce.py` reads the device's operations and the
benchmark's spans (`bench.*`).  This module also reads what the program
records itself (`src/repro/trace.py`): its host spans (`engn.*`) and
the stage scope of every device operation.

`load(path)` returns what `trace_reduce.load` returns, with one field
more on each event:
    host    [name, start_ns, duration_ns, thread] for every span whose
            name starts with "bench." or "engn.", `thread` naming its
            line of the host plane as "<line name>/<index>" (the lines
            of Python threads all share the name "python");
    device  [label, start_ns, duration_ns, op_name]: the `op_name`
            metadata of the operation's HLO instruction, for example
            "jit(train_step)/transpose(jvp(engn.aggregate))/...".  The
            events carry none of it (a TPU op event holds its whole HLO
            instruction as its name, and only its offset and duration
            as stats); the profiler keeps each module's HLO proto in its
            "/host:metadata" plane, under the module's name as the
            "XLA Modules" line shows it, which `op_names` decodes.

`reduce(events)` returns `trace_reduce.reduce`'s numbers, which the
three-field events of `trace_reduce.load` still give unchanged, with:
    span_self_s  seconds per span name within the window, less the part
                 covered by spans of the same thread nested inside it;
    scope_s      device seconds per program stage: the innermost
                 "engn.<stage>" scope of each operation's op_name,
                 "unscoped" for the rest, summed over devices;
    idle_gaps    each named by the innermost span open at the gap's
                 middle on the thread that holds "bench.window" (as
                 trace_reduce names it), then " + " the innermost span
                 open at that moment on each other thread.

Run as a script, it takes one traced window of a cell as `bench/run.py
--trace 1` does, reduces it here and prints one JSON line: the stage
metrics (`bench/metrics/`, listed in STAGE_METRICS), the window's
host-clock times (TIMES) and the reduction.

    python3 bench/trace_stages.py --workload <cell> --seed <n> \
        --seconds <s> [--dump <events.json.gz>]
"""
from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402

PREFIXES = ("bench.", "engn.")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"engn\.[a-z_]+")

# the host-clock times of the traced window, beside the untraced runs
# for the cost of tracing
TIMES = ("train_step_ms", "serve_p50_ms", "serve_p95_ms")
STAGE_METRICS = ("extract_ms_per_step.train", "aggregate_ms_per_step.train",
                 "queue_wait_ms.serve", "extract_ms_per_batch.serve",
                 "stage_ms_per_batch.serve", "infer_ms_per_batch.serve",
                 "bucket_fill.serve", "hit_batch_wait_ms.serve")


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field;
    fixed-width fields are skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            i += 8 if kind == 1 else 4
            continue
        yield key >> 3, value


def _field(buf, number: int, default=b""):
    return next((v for n, v in _fields(buf) if n == number), default)


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{module name: {HLO instruction name: op_name}} from the HLO protos
    of an xplane file's metadata plane.  The field numbers are those of
    tsl's xplane.proto (XSpace.planes 1; XPlane.name 2, .event_metadata
    4, .stat_metadata 5; map entries key 1, value 2; XEventMetadata.name
    2, .stats 5; XStat.metadata_id 1, .bytes_value 6; XStatMetadata.id
    1, .name 2) and XLA's hlo.proto (HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2)."""
    data = memoryview(Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(data):
        if number != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        stat_ids = {_field(_field(e, 2), 1, 0)
                    for n, e in _fields(plane) if n == 5
                    and _text(_field(_field(e, 2), 2)) == HLO_PROTO_STAT}
        for n, entry in _fields(plane):
            if n != 4:
                continue
            meta = _field(entry, 2)
            for k, stat in _fields(meta):
                if k == 5 and _field(stat, 1, 0) in stat_ids:
                    out[_text(_field(meta, 2))] = _instruction_op_names(
                        _field(stat, 6))
    return out


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    names: Dict[str, str] = {}
    for comp in (c for n, c in _fields(_field(hlo_proto, 1)) if n == 3):
        for inst in (i for n, i in _fields(comp) if n == 2):
            op_name = _text(_field(_field(inst, 7), 2))
            if op_name:
                names[_text(_field(inst, 1))] = op_name
    return names


def _instruction(hlo: str) -> str:
    m = trace_reduce._HLO.match(hlo)
    return m.group(1) if m else hlo


def load(path: str, devices: Optional[Iterable[int]] = None) -> Dict:
    """The device operations with their op_name, and the benchmark's and
    the program's host spans with their thread, of one xplane file."""
    from bisect import bisect_right

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    names = op_names(path)
    keep = None if devices is None else {int(d) for d in devices}
    out: Dict = {"device": {}, "modules": {}, "host": []}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m and (keep is None or int(m.group(1)) in keep):
            lines = {line.name: line for line in plane.lines}
            runs = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                           ev.name)
                          for ev in (lines[trace_reduce.MODULES_LINE].events
                                     if trace_reduce.MODULES_LINE in lines
                                     else ()))
            starts = [r[0] for r in runs]

            def op_name(ev):
                k = bisect_right(starts, int(ev.start_ns)) - 1
                if k < 0 or ev.start_ns >= runs[k][1]:
                    return ""
                return names.get(runs[k][2], {}).get(
                    _instruction(ev.name), "")
            if trace_reduce.OPS_LINE in lines:
                out["device"][plane.name] = [
                    [trace_reduce.label(ev.name), int(ev.start_ns),
                     int(ev.duration_ns), op_name(ev)]
                    for ev in lines[trace_reduce.OPS_LINE].events]
            out["modules"][plane.name] = [
                [trace_reduce.label(name), start, end - start]
                for start, end, name in runs]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out["host"].extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     f"{line.name}/{i}"]
                    for ev in line.events if ev.name.startswith(PREFIXES))
    return out


def stage_of(op_name: str) -> str:
    """The innermost program scope in an op_name, else "unscoped"."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def _thread(span: List) -> Optional[str]:
    return span[3] if len(span) > 3 else None


def span_self_seconds(host: List, w0: int, w1: int) -> Dict[str, float]:
    """Seconds per span name inside [w0, w1], less its direct children
    on the same thread (spans of one thread nest, so the children of one
    span do not overlap)."""
    self_ns: Dict[str, int] = {}

    def close(entry):
        self_ns[entry[0]] = self_ns.get(entry[0], 0) + max(entry[2], 0)
    by_thread: Dict[Optional[str], List] = {}
    for s in host:
        if s[0] != trace_reduce.WINDOW_SPAN:
            by_thread.setdefault(_thread(s), []).append(s)
    for spans in by_thread.values():
        stack: List = []                    # [name, end, self length]
        for name, start, dur, *_ in sorted(spans,
                                           key=lambda s: (s[1], -s[2])):
            while stack and stack[-1][1] <= start:
                close(stack.pop())
            length = max(0, min(start + dur, w1) - max(start, w0))
            if stack:
                stack[-1][2] -= length
            stack.append([name, start + dur, length])
        for entry in stack:
            close(entry)
    return {k: v * 1e-9 for k, v in self_ns.items() if v > 0}


def scope_seconds(device: Dict, w0: int, w1: int) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for evs in device.values():
        for ev in evs:
            _, s, d = ev[:3]
            if s + d > w0 and s < w1:
                stage = stage_of(ev[3]) if len(ev) > 3 else UNSCOPED
                out[stage] = out.get(stage, 0.0) + d * 1e-9
    return out


def name_at(host: List, t: int) -> str:
    """The innermost span open at t on the window's thread (or what
    trace_reduce names there), then " + " that of each other thread."""
    window = next(s for s in host if s[0] == trace_reduce.WINDOW_SPAN)
    home = _thread(window)
    others: Dict[Optional[str], List] = {}
    for s in host:
        if _thread(s) != home and s[1] <= t <= s[1] + s[2]:
            best = others.get(_thread(s))
            if best is None or s[2] < best[2]:
                others[_thread(s)] = s
    name = trace_reduce._span_at([s[:3] for s in host
                                  if _thread(s) == home], t)
    return " + ".join([name] + [others[k][0] for k in sorted(others)])


def reduce(events: Dict) -> Dict:
    base = {"device": {k: [ev[:3] for ev in v]
                       for k, v in events["device"].items()},
            "modules": events.get("modules", {}),
            "host": [s[:3] for s in events["host"]]}
    out = trace_reduce.reduce(base)
    w0, w1 = trace_reduce.window_of(base)
    out["span_self_s"] = span_self_seconds(events["host"], w0, w1)
    out["scope_s"] = scope_seconds(events["device"], w0, w1)
    gaps = _gaps(base, w0, w1)
    out["idle_gaps"] = [[name_at(events["host"], (a + b) // 2),
                         (b - a) * 1e-9] for a, b in gaps]
    return out


def _gaps(base: Dict, w0: int, w1: int) -> List:
    """The longest stretches with no operation on a device, as
    trace_reduce finds them."""
    gaps = []
    for _, evs in sorted(base["device"].items()):
        busy = trace_reduce._union([(max(s, w0), min(s + d, w1))
                                    for _, s, d in evs
                                    if s + d > w0 and s < w1])
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps.extend((a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a)
    return sorted(gaps, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]


def traced_window(c: Dict, seed: int, seconds: float, devices: list,
                  dump: Optional[str] = None) -> Dict:
    """Set up the cell whose files are `c`, trace one window of it on
    `devices`, and return the reduction and the stage metrics read from
    it.  `dump` keeps the loaded events there (gzipped JSON)."""
    import gzip
    import importlib
    import json
    import shutil

    import jax

    from bench import harness
    job = importlib.import_module(f"bench.jobs.{c['workload']['job']}")
    ctx = job.setup(c, seed)
    trace_dir = harness.BENCH / ".trace_stages"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        rec = job.window(ctx, seconds)
    finally:
        jax.profiler.stop_trace()
    if "pipe" in ctx:
        rec["latency"] = ctx["pipe"].telemetry()["latency"]
    try:
        path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
        events = load(path, devices=[d.id for d in devices])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        job.release(ctx)
    if dump:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(dump, "wt") as f:
            json.dump(events, f)
    red = reduce(events)
    rec.update(job=c["workload"]["job"], trace=red)
    metrics = {name: harness.module("metrics", name).read(rec)
               for name in STAGE_METRICS + TIMES}
    return {"metrics": {k: v for k, v in metrics.items() if v is not None},
            "reduced": red, "rec": rec}


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness
    harness.add_program_to_path()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", help="keep the loaded events here (.json.gz)")
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    try:
        devices = harness.chips(c["entry"]["chips"])
    except harness.NoChip as e:
        print(f"bench/trace_stages.py: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = traced_window(c, args.seed, args.seconds, devices, args.dump)
    red, rec = out["reduced"], out["rec"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "metrics": out["metrics"],
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "scope_s": red["scope_s"], "span_self_s": red["span_self_s"],
        "spans": red["spans"], "modules": red["modules"],
        "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
        "engine": rec.get("engine"), "pipeline": rec.get("pipeline"),
        "latency": rec.get("latency")}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
