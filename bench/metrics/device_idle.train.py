"""device_idle.train: the device's idle share of a traced training
window, in percent."""
from bench.metrics._idle import idle_percent


def read(rec):
    return idle_percent(rec, "train")
