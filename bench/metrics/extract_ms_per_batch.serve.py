"""extract_ms_per_batch.serve: host milliseconds of one L-hop
extraction: the self time of the program's `engn.serve.extract` spans
(the walk on an extraction worker) over their number."""
from bench.metrics._stages import span_ms

EXTRACT = "engn.serve.extract"


def read(rec):
    return span_ms(rec, [EXTRACT], per=EXTRACT)
