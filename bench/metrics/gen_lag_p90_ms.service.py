"""gen_lag_p90_ms.service: 90th percentile of how late each submit ran
after its due time, in milliseconds."""
from bench.readers import percentile


def read(m):
    return percentile((1e3 * (r.submit_s - r.request.due_s)
                       for r in m.records if r.submit_s == r.submit_s), 90)
