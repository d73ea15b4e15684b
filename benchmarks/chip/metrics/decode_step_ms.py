"""Device time of the decode chunk-scan programs (``_chunk_scan``,
``_chunk_scan_paged``) in the trace, divided by the decode steps they ran
(executions x decode_chunk)."""
PROGRAMS = ("_chunk_scan", "_chunk_scan_paged")


def read(w):
    n, s = w.program(PROGRAMS)
    if not n:
        return None
    return s / (n * w.decode_chunk) * 1e3
