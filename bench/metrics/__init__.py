"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has `read(rec) -> float | None`: `rec` is the run's record
(host-clock times, the program's counters, the device's peak memory and,
in a traced run, the reduced trace).  A reader that finds nothing to
read returns None and the metric is left out of the result line; a
share of a peak or a roofline is never reported as 0 for want of data.
"""
