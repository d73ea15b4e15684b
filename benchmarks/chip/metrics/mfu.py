"""Model FLOP/s utilisation of the whole window: work.py's model FLOPs
for every prompt and output token processed in the traced window,
divided by (traced window x peak FLOP/s)."""
from benchmarks.chip import work


def read(w):
    if not w.trace or not w.peak_flops or w.trace["window_s"] <= 0:
        return None
    flops = work.tokens_flops(w.shapes, w.processed_contexts(upto=w.trace_end))
    return flops / (w.trace["window_s"] * w.peak_flops) * 100.0
