"""fingerprint_mb: megabytes (1e6 B) of training Hessians copied to the
host and hashed for the factor cache's keys, per problem of the window.
Each result reports the cache's cumulative ``fingerprint_bytes`` among its
cache stats; the reading is its growth over the problems after the first.
A program that does not count them gives nothing."""


def _count(record):
    engine = (getattr(record.result, "extras", None) or {}).get("engine")
    cache = (engine or {}).get("cache") or {}
    return cache.get("fingerprint_bytes")


def read(m):
    counts = [c for c in map(_count, m.records) if c is not None]
    if len(counts) < 2:
        return None
    return (counts[-1] - counts[0]) / (len(counts) - 1) / 1e6
