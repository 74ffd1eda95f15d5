"""Shared arithmetic of the metric readers in ``bench/metrics``.

Program and kernel names are the XLA names the program's jitted
functions get (seen in a trace of the chip): ``build_paged_decode_loop``'s
``loop`` and ``ServeEngine._prefill_fn``.  The paged-attention kernel
of ``kernels/paged_attn`` has no name of its own in the trace: it is a
``custom-call`` with target ``tpu_custom_call``, the only Pallas
kernel on these cells' path.
"""
from __future__ import annotations

from bench import counting

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


def idle_share(run) -> float | None:
    s = traced_summary(run)
    if s is None or run.traced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / run.traced["window_s"])


def served_flops(run, reqs) -> int:
    return sum(counting.request_flops(run.model, len(r.prompt), len(r.tokens))
               for r in reqs)


def served_kv_bytes(run, reqs) -> int:
    return sum(counting.decode_kv_bytes(run.model, len(r.prompt), len(r.tokens))
               for r in reqs)
