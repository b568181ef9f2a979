"""Median time to first token over every request of the window, from the
time it was due; a request that failed or never finished is a miss above
every limit. The median and not a tail: the seed orders the arrivals, and
the tail follows the order (it swung 2.5 times from seed to seed), while
the median follows the load."""
from stats import percentile


def read(run):
    return percentile(run.work.get("ttft_s", []), 50)
