"""Mean recall@10 of the approximate answers against the f64 reference,
over every request due in the window that was answered."""


def read(win):
    return win.recall
