"""Time from a request's due time to the end of the tick whose
``req.dispatched`` event names it; mean over the requests dispatched in
the window."""
import numpy as np


def read(w):
    waits = []
    for rid, t in w.dispatched_t.items():
        end = w.tick_end(t)
        if end is not None and end <= w.close and rid in w.served:
            waits.append((end - w.served[rid].due) * 1e3)
    return float(np.mean(waits)) if waits else None
