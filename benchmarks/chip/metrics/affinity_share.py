"""Share of the requests the dispatcher placed in the window that it
placed by cached prefix: ``Dispatcher.affinity_placements`` over the
requests it dispatched (``dispatched_per_tier``, summed), both gained
over the window, %."""


def read(w):
    if not w.counters.get("dispatched"):
        return None
    return w.counters["affinity_placements"] / w.counters["dispatched"] * 100.0
