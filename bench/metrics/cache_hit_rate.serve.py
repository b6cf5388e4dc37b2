"""cache_hit_rate.serve: the result cache's hits over its probes in the
window (DegreeAwareCache stats), in percent."""


def read(rec):
    cache = rec.get("cache") or {}
    probes = cache.get("hits", 0) + cache.get("misses", 0)
    if rec.get("job") != "serve" or probes == 0:
        return None
    return 100.0 * cache["hits"] / probes
