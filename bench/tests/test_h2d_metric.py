"""The feature-staging metric read from hand-built serving records."""
import pytest

from bench import harness


def _read(rec):
    return harness.module("metrics", "h2d_mb_per_batch.serve").read(rec)


def test_megabytes_per_device_batch():
    rec = {"job": "serve",
           "engine": {"h2d_bytes": 27_200_000, "device_batches": 4}}
    assert _read(rec) == pytest.approx(6.8)


@pytest.mark.parametrize("rec", [
    {"job": "serve", "engine": {"device_batches": 4}},       # no counter
    {"job": "serve", "engine": {"h2d_bytes": 0, "device_batches": 0}},
    {"job": "train", "engine": {"h2d_bytes": 8, "device_batches": 1}},
    {}], ids=["parent", "no-batches", "train", "empty"])
def test_nothing_to_read(rec):
    assert _read(rec) is None
