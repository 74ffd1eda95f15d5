"""Output tokens delivered in the window over the whole window (host clock)."""


def read(run):
    return sum(len(r.tokens) for r in run.requests) / run.window_s
