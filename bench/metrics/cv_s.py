"""cv_s: window seconds over the CV problems completed in it."""


def read(m):
    return m.window_s / len(m.records) if m.records else None
