"""Device time of the mixed-step programs (``_mixed_step_fn``,
``_mixed_step_paged_fn``: chunked prefill fused with decode) in the
trace, divided by their executions."""
PROGRAMS = ("_mixed_step_fn", "_mixed_step_paged_fn")


def read(w):
    n, s = w.program(PROGRAMS)
    if not n:
        return None
    return s / n * 1e3
