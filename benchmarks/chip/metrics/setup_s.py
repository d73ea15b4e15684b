"""Set-up: process start to the window's open (loading, weights, warm-up
and, in a run that compiles, compilation)."""


def read(w):
    return w.setup_s
