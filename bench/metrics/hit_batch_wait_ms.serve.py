"""hit_batch_wait_ms.serve: the mean milliseconds from admission to
completion of a batch that the cache answered whole, which FIFO
completion spends behind the miss batches ahead of it (the pipeline's
`hit_batch_wait_s` over its `hit_batches`)."""


def read(rec):
    pipe = rec.get("pipeline") or {}
    if rec.get("job") != "serve" or not pipe.get("hit_batches"):
        return None
    return 1e3 * pipe["hit_batch_wait_s"] / pipe["hit_batches"]
