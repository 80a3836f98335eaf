"""chol_ms: device milliseconds per problem of the anchor factorizations."""
from bench.readers import layer_s_per_problem


def read(m):
    s = layer_s_per_problem(m, "chol")
    return None if s is None else 1e3 * s
