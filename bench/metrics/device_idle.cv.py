"""device_idle.cv: share of the traced window in which no op ran on the
device, in a CV-problem cell."""
from bench.readers import idle_pct


def read(m):
    return idle_pct(m)
