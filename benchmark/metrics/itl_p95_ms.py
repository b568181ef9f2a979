"""95th percentile of the gaps between consecutive tokens of every
request, as the host got them: decode steps, and prefills that ran
between two of a request's decode steps."""
from stats import percentile


def read(run):
    p = percentile(run.work.get("itl_s", []), 95)
    return None if p is None else 1e3 * p
