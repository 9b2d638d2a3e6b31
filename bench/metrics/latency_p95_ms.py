"""95th percentile of client latency, from each request's due time to its
answer; a failed request counts as infinitely late."""
from bench.readings import latency_percentile


def read(win):
    return latency_percentile(win, 95)
