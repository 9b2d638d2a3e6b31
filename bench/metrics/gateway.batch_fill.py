"""Mean over formed batches of real queries per padded rung. Each answer
carries its batch's size and rung, so a batch of b answers adds b / b = 1
to the count and b / rung to the sum."""
from bench.readings import answered


def read(win):
    resps = answered(win)
    if not resps:
        return None
    n_batches = sum(1.0 / r.batch_size for r in resps)
    return sum(1.0 / r.padded_to for r in resps) / n_batches
