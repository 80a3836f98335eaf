"""batch_mean.service: mean requests per server dispatch in the window."""


def read(m):
    d = m.counters.get("dispatches", 0)
    return m.counters["served"] / d if d else None
