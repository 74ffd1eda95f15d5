"""Shared arithmetic of the metric readers in ``bench/metrics``.

Program and kernel names are the XLA names the program's jitted
functions get (seen in a trace of the chip): ``build_paged_decode_loop``'s
``loop`` and ``ServeEngine._prefill_fn``.  The paged-attention kernel
of ``kernels/paged_attn`` is named ``paged_attention`` in the trace
(``paged_attention.12_custom-call_..._tpu_custom_call`` on a v5e); its
reader keys on the custom call's target, ``tpu_custom_call``, since it
is the only Pallas kernel on these cells' path.  The traced call's
length is that of its ``serve.run`` span (``bench/engine_trace.py``),
which holds the engine's work and not the profiler's start and stop.
"""
from __future__ import annotations

from bench import counting, engine_trace

DECODE_LOOP = r"^jit_loop$"
PREFILL = r"^jit__prefill_fn$"
PAGED_ATTN_KERNEL = r"tpu_custom_call$"


def traced_summary(run):
    return run.traced["summary"] if run.traced else None


def decode_step_ms(run) -> float | None:
    s = traced_summary(run)
    steps = run.traced["stats"]["decode_steps"] if run.traced else 0
    t = s.program_s(DECODE_LOOP) if s else None
    if t is None or steps <= 0:
        return None
    return 1e3 * t / steps


def traced_run_s(run) -> float | None:
    """Seconds of the traced call's ``serve.run`` span, on the trace's
    clock; None for an untraced run or a trace without the span."""
    s = engine_trace.summary(run)
    return s.span_s(engine_trace.SPANS.RUN) if s else None


def served_flops(run, reqs) -> int:
    return sum(counting.request_flops(run.model, len(r.prompt), len(r.tokens))
               for r in reqs)


def served_kv_bytes(run, reqs) -> int:
    return sum(counting.decode_kv_bytes(run.model, len(r.prompt), len(r.tokens))
               for r in reqs)
