"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / traced window."""


def read(w):
    if not w.trace or not w.trace["devices"] or w.trace["window_s"] <= 0:
        return None
    return (1.0 - w.trace["busy_s"] / w.trace["window_s"]) * 100.0
