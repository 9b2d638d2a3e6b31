"""Seconds from process start to the window: data, ingest, arena upload,
prewarm, compilation where the cache misses, and the warm-up traffic."""


def read(win):
    return win.setup_s
