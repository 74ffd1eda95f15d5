"""Serve engine decode loop (pool movement): leaf-op time of the
decode-loop program that no scope of the program claims -- the layer
scan's and the while-loop carry's movement of the stacked KV pool --
over all its leaf-op time, in %."""
from bench import engine_trace


def read(run):
    s = engine_trace.summary(run)
    return s.scope_share("") if s else None
