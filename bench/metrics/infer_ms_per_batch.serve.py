"""infer_ms_per_batch.serve: milliseconds of one device batch, from its
dispatch until its rows are on the host (transfers in, the bucket's
program, the transfer out): the self time of the program's
`engn.serve.infer` spans over their number."""
from bench.metrics._stages import span_ms

INFER = "engn.serve.infer"


def read(rec):
    return span_ms(rec, [INFER], per=INFER)
