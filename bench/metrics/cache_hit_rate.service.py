"""cache_hit_rate.service: factor-cache hits over lookups in the window,
from FactorCache's own counters, in percent."""


def read(m):
    hits, misses = m.counters.get("hits", 0), m.counters.get("misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
