"""Share of the window the feeder spent inside ``StreamingIndex.ingest``,
by the benchmark's own clock."""
from bench.readings import in_window


def read(win):
    if not win.feeds:
        return None
    inside = sum(in_window(win, t_in, t_out)
                 for _, t_in, t_out, _, _ in win.feeds)
    return inside / win.seconds
