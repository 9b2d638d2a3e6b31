"""Series whose ingest() returned inside the window, over its length."""


def read(win):
    if not win.feeds:
        return None
    rows = sum(n for _, _, done, n, _ in win.feeds if 0 <= done < win.seconds)
    return rows / win.seconds
