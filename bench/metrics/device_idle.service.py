"""device_idle.service: share of the traced window in which no op ran on
the device, in a service cell."""
from bench.readers import idle_pct


def read(m):
    return idle_pct(m)
