"""90th percentile of the wait from a request's due time to its
admission into a decode lane; a request that failed is a miss. The 90th,
the highest with ten or more of a window's requests beyond it."""
from stats import percentile


def read(run):
    return percentile(run.work.get("queue_s", []), 90)
