"""Share of the prompt tokens admitted in the window that the program
served from cached pages: the engines' ``EngineTelemetry.reused_tokens``
(the sum of each pump's ``PumpReport.reused_tokens``) gained over the
window, over the prompt lengths of the requests its ``req.admitted``
events name in the window's ticks, %."""


def read(w):
    if not w.admitted_prompt_tokens or "reused_tokens" not in w.counters:
        return None
    return w.counters["reused_tokens"] / w.admitted_prompt_tokens * 100.0
