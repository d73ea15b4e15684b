"""Output tokens delivered to the client inside the window, divided by
the window."""


def read(w):
    n = sum(1 for s in w.served.values() for t in s.stamps if t <= w.close)
    return n / w.seconds
