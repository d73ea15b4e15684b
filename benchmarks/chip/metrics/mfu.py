"""Model FLOP/s utilisation of the whole window: the work model's FLOPs
for every prompt and output token processed in the traced window,
divided by (traced window x peak FLOP/s)."""


def read(w):
    if not w.trace or not w.peak_flops or w.trace["window_s"] <= 0:
        return None
    flops = w.shapes.tokens_flops(w.processed_contexts(upto=w.trace_end))
    return flops / (w.trace["window_s"] * w.peak_flops) * 100.0
