"""Mean ``n_blocks`` (blocks read per query and run) that the recommender
chose for the window's approximate answers."""
import numpy as np

from bench.readings import answered


def read(win):
    nb = [r.n_blocks for r in answered(win) if r.tier_served == "approx"]
    return float(np.mean(nb)) if nb else None
