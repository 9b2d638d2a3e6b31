"""Largest ``ingest_lag()['lag_entries']`` (ingested but not yet in a
published run) read after each ingest call that returned in the window."""


def read(win):
    lags = [lag for _, _, done, _, lag in win.feeds if 0 <= done < win.seconds]
    return max(lags) if lags else None
