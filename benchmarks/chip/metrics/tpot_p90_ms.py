"""90th percentile over requests of (last token time - first token time)
/ (tokens - 1), counting the tokens each request due in the window had
delivered by its close, for those with at least 2."""
import numpy as np


def read(w):
    per = []
    for s in w.served.values():
        st = [t for t in s.stamps if t <= w.close]
        if len(st) >= 2:
            per.append((st[-1] - st[0]) / (len(st) - 1) * 1e3)
    return float(np.percentile(per, 90)) if per else None
