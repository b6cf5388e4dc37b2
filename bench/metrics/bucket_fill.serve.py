"""bucket_fill.serve: the share of the rows the device ran that were
real subgraph vertices: the engine's `subgraph_vertices` over its
`padded_vertices` (each batch counted at its shape bucket's rows), in
percent."""


def read(rec):
    eng = rec.get("engine") or {}
    if rec.get("job") != "serve" or not eng.get("padded_vertices"):
        return None
    return 100.0 * eng["subgraph_vertices"] / eng["padded_vertices"]
