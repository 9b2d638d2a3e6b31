"""Median client latency, from each request's due time to its answer."""
from bench.readings import latency_percentile


def read(win):
    return latency_percentile(win, 50)
