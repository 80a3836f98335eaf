"""interp_ms: device milliseconds per problem of the λ stage
(interp_solve)."""
from bench.readers import layer_s_per_problem


def read(m):
    s = layer_s_per_problem(m, "interp")
    return None if s is None else 1e3 * s
