"""Median, over every request due in the window, of the time from its due
time to its first token at the client.  A request with no first token
when the window closes enters with its wait so far."""
import numpy as np


def read(w):
    waits = []
    for s in w.served.values():
        first = s.stamps[0] if s.stamps and s.stamps[0] <= w.close else w.close
        waits.append(first - s.due)
    return float(np.median(waits)) if waits else None
