"""Share of the screen kernel's roofline over the traced window: the least
time of every ``screen_select`` launch, from its shapes and the device's
peaks (``bench/roofline.py``), over the launches' summed device time.
Launches whose text carries no shapes count on neither side."""
from bench import roofline


def read(win):
    launches = [] if win.trace is None else \
        win.trace["kernel_ops"]["screen_select"]
    if not launches:
        return None
    peaks = roofline.load_peaks(win.device_kind)
    least = spent = 0.0
    for hlo, seconds in launches:
        shapes = roofline.screen_shapes(hlo)
        if shapes is None:
            continue
        least += roofline.least_seconds(*roofline.screen_cost(*shapes),
                                        peaks)[0]
        spent += seconds
    return 100.0 * least / spent if spent else None
