"""Host seconds in the functional simulator (``engine.interpret`` spans)
per million simulated instructions."""


def read(r):
    minstr = r.counter("capsim_frontend_instructions_total") / 1e6
    if not minstr:
        return None
    return r.span_s("engine.interpret") / minstr
