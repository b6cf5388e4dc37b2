"""setup_s: seconds from the start of the process to the first timed
step: imports, graph, inputs, the program's preparation, compiles or
cache loads, and warm-up (host clock)."""


def read(rec):
    return rec.get("setup_s")
