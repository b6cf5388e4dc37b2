"""The program's spans and stage scopes, for a profiler trace.

`span(name, **args)` is a host span: a `jax.profiler.TraceAnnotation`,
so it lands in the profiler's host plane, on the line of the thread
that opened it and on the same clock as the device ops.  Its keyword
arguments (`batch=<ticket>`) become the event's stats, which join the
spans of one serving batch across threads.  With no trace being taken
it costs one check.

`scope(stage)` is a device scope: a `jax.named_scope`, which only adds
the stage to the `op_name` metadata of the ops traced inside it, so it
costs nothing at run time.  The backward pass keeps the scope of the
forward op it differentiates (`transpose(jvp(engn.aggregate))`), custom
VJP rules included.
"""
from __future__ import annotations

import jax

# device scopes: the three EnGN stages (paper Sec. 3) and the optimizer
EXTRACT = "engn.extract"
AGGREGATE = "engn.aggregate"
UPDATE = "engn.update"
OPTIMIZER = "engn.optimizer"
# nested in AGGREGATE: the segment edge-chunk loops that hold their
# narrow operands lane-packed (core/engn.py::reduce_edges); not an
# `engn.` name, so their ops still count under AGGREGATE
LANE_PACKED = "lane_packed"

# host spans of one serving batch, in the order the batch meets them
SERVE_PROBE = "engn.serve.probe"        # cache lookup and miss set
SERVE_EXTRACT = "engn.serve.extract"    # L-hop walk (extraction worker)
SERVE_GATHER = "engn.serve.gather"      # input feature rows of the walk
SERVE_PAD = "engn.serve.pad"            # padding into the shape bucket
SERVE_INFER = "engn.serve.infer"        # dispatch until rows are on host
SERVE_FINISH = "engn.serve.finish"      # cache insert and scatter

SERVE_SPANS = (SERVE_PROBE, SERVE_EXTRACT, SERVE_GATHER, SERVE_PAD,
               SERVE_INFER, SERVE_FINISH)


def span(name: str, **args):
    """A host span named `name`, with `args` as its stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def scope(stage: str):
    """A device scope: the ops traced inside carry `stage`."""
    return jax.named_scope(stage)
