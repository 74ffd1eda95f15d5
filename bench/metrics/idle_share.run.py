"""Device: 1 - (union of device op intervals inside the traced call's
``serve.run`` span / that span's length), in %, on the trace's clock.
The profiler's start and stop lie outside the span."""
from bench import engine_trace


def read(run):
    s = engine_trace.summary(run)
    return s.idle_share_in(engine_trace.SPANS.RUN) if s else None
