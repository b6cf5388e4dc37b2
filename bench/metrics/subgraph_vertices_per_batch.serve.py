"""subgraph_vertices_per_batch.serve: vertices of the L-hop subgraphs
the engine extracted in the window, per extraction (engine.stats)."""


def read(rec):
    eng = rec.get("engine") or {}
    if rec.get("job") != "serve" or not eng.get("subgraphs"):
        return None
    return eng["subgraph_vertices"] / eng["subgraphs"]
