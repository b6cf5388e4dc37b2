"""stage_ms_per_batch.serve: host milliseconds of feature staging per
device batch: the self time of the program's `engn.serve.gather` (the
walk's feature rows) and `engn.serve.pad` (padding into the shape
bucket) spans, over the `engn.serve.infer` spans."""
from bench.metrics._stages import span_ms


def read(rec):
    return span_ms(rec, ["engn.serve.gather", "engn.serve.pad"],
                   per="engn.serve.infer")
