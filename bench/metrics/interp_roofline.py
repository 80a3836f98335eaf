"""interp_roofline: least time of the λ stage (Θ read once per fold per
substitution sweep, with its right-hand sides and solutions) over its
device time, in percent."""
from bench.readers import roofline_pct


def read(m):
    return roofline_pct(m, "interp")
