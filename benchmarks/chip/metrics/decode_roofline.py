"""Share of its roofline that the decode step as a whole reaches: the
larger of the work model's FLOPs over peak FLOP/s and its bytes over
peak B/s for the decode steps traced (the weights a step reads, plus the
live KV of each active slot), divided by the chunk-scan programs' device
time."""
from benchmarks.chip import work

PROGRAMS = ("_chunk_scan", "_chunk_scan_paged")


def read(w):
    n, s = w.program(PROGRAMS)
    if not n or s <= 0 or not w.peak_flops:
        return None
    ctx = w.decode_contexts(upto=w.trace_end)
    flops = w.shapes.tokens_flops(ctx)
    nbytes = w.shapes.decode_steps_bytes(int(n) * w.decode_chunk, ctx)
    return work.roofline_s(flops, nbytes, w.peak_flops, w.peak_bytes_s) / s * 100.0
