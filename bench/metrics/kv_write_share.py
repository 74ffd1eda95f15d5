"""FRAC KV write: leaf-op time under ``decode.attn/kv_write`` (the
slot's FRAC fake-quant and its page write) over all leaf-op time of the
decode-loop program, in %."""
from bench import engine_trace


def read(run):
    s = engine_trace.summary(run)
    return s.scope_share(engine_trace.SPANS.KV_WRITE_PATH) if s else None
