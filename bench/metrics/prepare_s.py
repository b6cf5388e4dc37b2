"""prepare_s: host seconds of the program's graph preparation, the
construction of the trainer (prepare_graph and the step's jit) or of
the serving engine (CSR and cache)."""


def read(rec):
    return rec.get("prepare_s")
