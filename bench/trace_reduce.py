"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second is tested on a recorded trace:

`load(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` and
keeps three things as plain lists of [name, start_ns, duration_ns]:
the operations on each device's "XLA Ops" line, the XLA modules on its
"XLA Modules" line, and the benchmark's own host spans (names starting
with "bench.").  Device and host events share the profiler's clock.

A TPU trace names an operation by its whole HLO instruction.  `label`
shortens it: a Pallas kernel (a `tpu_custom_call`) becomes "kernel "
and the name of the jitted function around it, without its instance
number, so every launch of one kernel shares a label (the RER kernels
show as "kernel jvp_jit__packed_spmm_pallas__" for rer_gather and
"kernel jvp_jit__blocked_spmm_jit__" for rer_spmm under training's
custom VJP); any other operation keeps its HLO name and output shape,
as "fusion.227 f32[65755,64]".

`reduce(events)` takes the traced window from the "bench.window" span
and returns:
    window_s     the window's length;
    busy_s       the union of the intervals in which an operation ran,
                 clipped to the window, averaged over the devices;
    ops          seconds per operation name, summed over devices;
    device_ops   the ten operations that took most time;
    idle_gaps    the ten longest stretches with no operation on a
                 device, each named by the innermost benchmark span open
                 at its middle ("none" when no span was open);
    spans        how many of each benchmark span the window holds;
    modules      how many runs of each XLA module began in the window,
                 over all devices, by the module's name ("jit_train_step").
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


_HLO = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")
KERNEL = "kernel "


def label(hlo: str) -> str:
    m = _HLO.match(hlo)
    if m is None:
        return hlo[:80]
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return KERNEL + re.sub(r"\.\d+$", "", m.group(1))
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def load(path: str, devices: Optional[Iterable[int]] = None) -> Dict:
    """The device operations and benchmark spans of one xplane file.
    `devices` limits the device planes to those chip ids."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    keep = None if devices is None else {int(d) for d in devices}
    out: Dict = {"device": {}, "modules": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (keep is None or int(m.group(1)) in keep):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = out["device" if line.name == OPS_LINE
                               else "modules"]
                    dest[plane.name] = [[label(ev.name), int(ev.start_ns),
                                         int(ev.duration_ns)]
                                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def window_of(events: Dict) -> Tuple[int, int]:
    spans = [s for s in events["host"] if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, start, dur = spans[0]
    return start, start + dur


def _span_at(host: List, t: int, exclude: str = WINDOW_SPAN) -> str:
    """The innermost (shortest) benchmark span open at time t, else
    "after " the span that closed last before t, else "none"."""
    best, last = None, None
    for name, start, dur in host:
        if name == exclude:
            continue
        if start <= t <= start + dur:
            if best is None or dur < best[1]:
                best = (name, dur)
        elif start + dur < t and (last is None or start + dur > last[1]):
            last = (name, start + dur)
    if best:
        return best[0]
    return f"after {last[0]}" if last else "none"


def reduce(events: Dict) -> Dict:
    w0, w1 = window_of(events)
    window_ns = w1 - w0
    busy_ns: List[int] = []
    ops: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for plane, evs in sorted(events["device"].items()):
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in evs
                   if s + d > w0 and s < w1]
        busy = _union(clipped)
        busy_ns.append(sum(b - a for a, b in busy))
        for name, s, d in evs:
            if s + d > w0 and s < w1:
                ops[name] = ops.get(name, 0.0) + d * 1e-9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps.extend((a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a)
    spans: Dict[str, int] = {}
    for name, start, dur in events["host"]:
        if name != WINDOW_SPAN and w0 <= start <= w1:
            spans[name] = spans.get(name, 0) + 1
    modules: Dict[str, int] = {}
    for evs in events.get("modules", {}).values():
        for name, start, dur in evs:
            if w0 <= start < w1:
                key = name.split("(")[0]
                modules[key] = modules.get(key, 0) + 1
    n_dev = max(len(busy_ns), 1)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) * 1e-9 / n_dev,
        "devices": len(busy_ns),
        "ops": ops,
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_span_at(events["host"], (a + b) // 2),
                       (b - a) * 1e-9]
                      for a, b in sorted(gaps, key=lambda g: g[0] - g[1])
                      [:TOP]],
        "spans": spans,
        "modules": modules,
    }


def op_seconds(reduced: Dict, pattern: str) -> float:
    """Seconds of the operations whose name matches `pattern`."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["ops"].items() if rx.search(k))
