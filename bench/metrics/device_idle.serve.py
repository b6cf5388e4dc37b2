"""device_idle.serve: the device's idle share of a traced serving
window, in percent."""
from bench.metrics._idle import idle_percent


def read(rec):
    return idle_percent(rec, "serve")
