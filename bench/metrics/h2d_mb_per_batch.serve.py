"""h2d_mb_per_batch.serve: megabytes the serving engine sent the device
per device batch in the window (its `h2d_bytes` counter over its
`device_batches`: features, edges and ids).  A program without the
counter gives None."""


def read(rec):
    eng = rec.get("engine") or {}
    if (rec.get("job") != "serve" or "h2d_bytes" not in eng
            or not eng.get("device_batches")):
        return None
    return eng["h2d_bytes"] / eng["device_batches"] / 1e6
