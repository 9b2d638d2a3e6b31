"""Share of device-screened queries that failed the f32 certificate and
were screened again on the host, counted over the window."""


def read(win):
    screened = win.engine["screened"]
    return win.engine["fallbacks"] / screened if screened else None
