"""MoE expert layer: leaf-op time of the decode-loop program under
``decode.moe`` and its children (``route``, ``experts``, ``shared``)
over all its leaf-op time, in %.  None for an untraced run, a program
that names no MoE scope, or a trace that holds no op of the expert
layer."""
from bench import engine_trace


def read(run):
    moe = getattr(engine_trace.SPANS, "MOE", None)
    s = engine_trace.summary(run) if moe else None
    if s is None:
        return None
    mine = [k for k in s.loop_scopes if k == moe or k.startswith(moe + "/")]
    return sum(s.scope_share(k) for k in mine) if mine else None
