"""req_per_s: requests completed inside the window over its seconds."""


def read(m):
    done = sum(1 for r in m.records
               if not r.error and r.result is not None
               and r.done_s <= m.window_s)
    return done / m.window_s if m.window_s > 0 else None
