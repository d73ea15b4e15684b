"""Mean ``engine.pump`` ``wall_s`` (one replica's admission, mixed steps
and decode chunk, host sync included) over the window's pumps."""
import numpy as np


def read(w):
    walls = [p.wall_s * 1e3 for p in w.pumps]
    return float(np.mean(walls)) if walls else None
