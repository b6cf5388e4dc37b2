"""`PreparedPlan` — the typed result of every `prepare_*` call (DESIGN.md C12).

`prepare_graph` / `prepare_tiled` / `prepare_ring` historically returned
ad-hoc dicts that callers key-probed (``gd.get("ring_meta") or
gd.get("tiled_meta")``, ``gd["blocks_meta"]["tile_format"]``, ...).  The
dict *contents* differ per backend by design — each backend carries its
own device operands — but the plan-level facts every caller wants are
the same five questions: which backend did I actually land on (spill
may have rerouted), which tile format, which streaming regime, how many
bytes does the plan claim, and what did the autotuner decide.

`PreparedPlan` answers those as typed attributes over the underlying
carrier dict.  The `MutableMapping` dict view that bridged dict-style
consumers for one release is gone: read the typed attributes, or reach
the backend operands through ``plan.carrier[...]`` / ``as_dict()`` /
``plan.meta``.  `plan_carrier` unwraps either a plan or a raw carrier
dict — the consumers that accept both (`EnGNLayer.apply`, the serving
engine's per-batch dicts) call it once at their entry point.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass(eq=False)
class PreparedPlan:
    """A prepared graph execution plan.

    backend:         the backend the plan actually targets — after any
                     budget spill, so ``backend`` may be "tiled" when
                     the config asked for "blocked"/"ring".
    tile_format:     "dense" | "packed" for the tile-carrying backends,
                     None for segment (no tiles).
    streaming_mode:  the tiled backend's landed regime ("chunk_queue" |
                     "callback"), None for device-resident backends.
    footprint_bytes: what the plan claims to occupy — device bytes for
                     resident backends (per *shard* for ring), host
                     store bytes + resident feature bytes for the
                     streamed tiled backend.  Best-effort: 0 when the
                     backend records no estimate (plain segment dicts).
    autotune:        the `kernels/autotune.py` FormatChoice record when
                     the tile format was autotuned, else None.
    carrier:         the backend-specific operand dict (device arrays,
                     executors, ring fns) — exactly the dict the
                     prepare_* functions used to return.
    """

    backend: str
    n: int
    carrier: Dict[str, Any]
    tile_format: Optional[str] = None
    streaming_mode: Optional[str] = None
    footprint_bytes: int = 0
    autotune: Optional[Any] = None

    def as_dict(self) -> Dict[str, Any]:
        """The raw carrier dict (not a copy)."""
        return self.carrier

    @property
    def meta(self) -> Dict[str, Any]:
        """The backend's meta block under one name: ``blocks_meta`` /
        ``tiled_meta`` / ``ring_meta``, or {} (segment carries none)."""
        return (self.carrier.get("blocks_meta")
                or self.carrier.get("tiled_meta")
                or self.carrier.get("ring_meta") or {})

    def __repr__(self) -> str:  # the carrier holds device arrays — elide
        return (f"PreparedPlan(backend={self.backend!r}, n={self.n}, "
                f"tile_format={self.tile_format!r}, "
                f"streaming_mode={self.streaming_mode!r}, "
                f"footprint_bytes={self.footprint_bytes}, "
                f"keys={sorted(self.carrier)})")


def plan_carrier(graph: Any) -> Dict[str, Any]:
    """The raw carrier dict of a plan-or-dict.  Dict-consuming code
    (`EnGNLayer.apply`, the serving engine's raw per-batch carriers)
    accepts either a `PreparedPlan` or a plain carrier dict; this is
    the one unwrap point."""
    return graph.carrier if isinstance(graph, PreparedPlan) else graph


def wrap_plan(carrier: Dict[str, Any]) -> PreparedPlan:
    """Build the typed plan over a prepare_* carrier dict, deriving the
    summary attributes from whichever meta block the backend wrote."""
    if isinstance(carrier, PreparedPlan):        # idempotent (spill paths
        return carrier                           # return wrapped plans)
    backend = carrier.get("backend", "segment")
    meta = (carrier.get("blocks_meta") or carrier.get("tiled_meta")
            or carrier.get("ring_meta") or {})
    footprint = int(meta.get("device_bytes") or 0)
    if not footprint and backend in ("blocked", "fused"):
        # dense block carriers predate the device_bytes estimate: price
        # the uploaded operands directly
        footprint = sum(int(getattr(v, "nbytes", 0))
                        for v in carrier.values())
    mode = meta.get("streaming_mode")
    if backend == "tiled":
        footprint = int(meta.get("host_bytes", 0)
                        + meta.get("resident_feature_bytes", 0))
        if mode == "auto":        # report the landed regime, not the ask
            mode = "chunk_queue" if meta.get("queue_plan") else "callback"
    return PreparedPlan(
        backend=backend,
        n=int(carrier.get("n", 0)),
        carrier=carrier,
        tile_format=meta.get("tile_format"),
        streaming_mode=mode,
        footprint_bytes=footprint,
        autotune=meta.get("format_choice"),
    )


def split_arrays(tree: Any) -> Tuple[List[jax.Array], Callable]:
    """`(arrays, rebuild)`: the `jax.Array` leaves of `tree` (a carrier
    dict, features, labels ...) and the function that puts replacements
    back.  A jitted function that closes over device arrays bakes them
    into its program as constants — a 1.2 GB dense tile plan becomes a
    1.2 GB literal to compile — so jitted steps take `arrays` as an
    argument and call `rebuild(arrays)` inside.  Every other leaf (ints,
    meshes, ring functions, executors) stays static."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, leaf in enumerate(leaves)
           if isinstance(leaf, jax.Array)]

    def rebuild(arrays):
        out = list(leaves)
        for i, a in zip(idx, arrays):
            out[i] = a
        return jax.tree_util.tree_unflatten(treedef, out)

    return [leaves[i] for i in idx], rebuild
