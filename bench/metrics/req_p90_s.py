"""req_p90_s: 90th percentile of completion less due time over every
request due in the window (the queue is drained after it)."""
from bench.readers import latencies, percentile


def read(m):
    return percentile(latencies(m), 90)
