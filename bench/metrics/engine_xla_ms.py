"""engine_xla_ms: device milliseconds per problem of every other op the
engine runs (split, Θ fit, packing, scoring)."""
from bench.readers import layer_s_per_problem


def read(m):
    s = layer_s_per_problem(m, "engine")
    return None if s is None else 1e3 * s
