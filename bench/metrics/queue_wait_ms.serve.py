"""queue_wait_ms.serve: the mean milliseconds a request waits from its
submission to its first admission into a batch (the batcher's
`mean_queue_delay_s`, under `latency` in the run's record)."""


def read(rec):
    lat = rec.get("latency")
    if rec.get("job") != "serve" or not isinstance(lat, dict) or not lat.get(
            "count"):
        return None
    return 1e3 * lat["mean_queue_delay_s"]
