"""Whole step: model FLOPs of the requests served in the traced group
(2 x matmul parameters per token processed, the head once per prompt
and once per decode token, attention over each token's context; see
``bench/counting.py``) over the traced call's ``serve.run`` span x
chips x peak bf16 FLOP/s, in %.  The span holds the whole ``run()``,
host work included, and not the profiler's start and stop."""
from bench import readers


def read(run):
    t = readers.traced_run_s(run)
    if t is None or run.peaks is None:
        return None
    flops = readers.served_flops(run, run.traced["requests"])
    return 100.0 * flops / (t * run.chips * run.peaks.flops_bf16)
