"""Mean ``engine.pump`` ``occupancy``: the share of slots decoding or
ingesting a prompt as each pump begins, over the window's pumps."""
import numpy as np


def read(w):
    occ = [p.occupancy * 100.0 for p in w.pumps]
    return float(np.mean(occ)) if occ else None
