"""Host time per control-loop tick outside the replicas' pumps: the
harness clock around each ``FleetClient.tick`` minus that tick's
``engine.pump`` ``wall_s``; mean over the window's ticks."""
import numpy as np


def read(w):
    pump = {}
    for p in w.pumps:
        pump[p.t] = pump.get(p.t, 0.0) + p.wall_s
    ticks = w.in_window_ticks()
    if not ticks:
        return None
    return float(np.mean([(tk.end - tk.start - pump.get(tk.t, 0.0)) * 1e3
                          for tk in ticks]))
