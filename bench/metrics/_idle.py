"""The device's idle share of the traced window: one minus the union of
the intervals in which an operation ran, averaged over the chips."""


def idle_percent(rec, job: str):
    tr = rec.get("trace")
    if (rec.get("job") != job or not tr or not tr["devices"]
            or tr["window_s"] <= 0):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
