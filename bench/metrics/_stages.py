"""The program's stage scopes and spans, read from a reduction that
holds them (`bench/trace_stages.py`: `scope_s`, `span_self_s`); a trace
reduced without them, or a program that records none, gives None."""

STEP_MODULE = "jit_train_step"


def ms_per_step(rec, scope: str):
    """Device milliseconds under `scope` per run of the train step."""
    tr = rec.get("trace")
    if rec.get("job") != "train" or not tr or scope not in tr.get(
            "scope_s", {}):
        return None
    steps = tr["modules"].get(STEP_MODULE, 0)
    return 1e3 * tr["scope_s"][scope] / steps if steps else None


def span_ms(rec, names, per: str):
    """Self milliseconds of the spans `names` per span `per` opened in
    the window."""
    tr = rec.get("trace")
    if rec.get("job") != "serve" or not tr or "span_self_s" not in tr:
        return None
    count = tr["spans"].get(per, 0)
    if not count:
        return None
    return 1e3 * sum(tr["span_self_s"].get(n, 0.0) for n in names) / count
