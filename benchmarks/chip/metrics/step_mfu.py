"""The whole step's share of the chip's peak: the work model's FLOPs for
every prompt and output token the steps processed in the traced window,
divided by the device time of all step programs (mixed steps and decode
chunk scans) times peak FLOP/s."""

PROGRAMS = ("_chunk_scan", "_chunk_scan_paged", "_mixed_step_fn",
            "_mixed_step_paged_fn")


def read(w):
    n, s = w.program(PROGRAMS)
    if not n or s <= 0 or not w.peak_flops:
        return None
    flops = w.shapes.tokens_flops(w.processed_contexts(upto=w.trace_end))
    return flops / (s * w.peak_flops) * 100.0
