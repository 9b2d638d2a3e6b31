"""Candidate rows the device passes asked for over the rows they screened
(``VerifyEngine.stats`` ``candidates`` over ``gathered_rows``, counted over
the window): what bucket padding leaves of each gather."""


def read(win):
    cand, gathered = win.engine.get("candidates"), win.engine.get("gathered_rows")
    return cand / gathered if cand is not None and gathered else None
