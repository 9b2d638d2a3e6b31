"""Share of the traced window in which no operation ran on the device."""


def read(win):
    if win.trace is None or not win.trace["window_s"]:
        return None
    return 1.0 - win.trace["busy_s"] / win.trace["window_s"]
