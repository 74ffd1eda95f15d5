"""Serve engine decode loop: decode tokens over decode steps times
lanes (``ServeStats`` over the window), in %.  A request's first token
comes from prefill, so it decodes ``len(tokens) - 1``."""


def read(run):
    steps = run.stats["decode_steps"]
    if steps <= 0:
        return None
    dec = sum(max(len(r.tokens) - 1, 0) for r in run.requests)
    return 100.0 * dec / (steps * run.engine["max_batch"])
