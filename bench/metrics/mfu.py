"""Whole step: model FLOPs of the requests served in the traced group
(2 x matmul parameters per token processed, the head once per prompt
and once per decode token, attention over each token's context; see
``bench/counting.py``) over the traced window x chips x peak bf16
FLOP/s, in %."""
from bench import readers


def read(run):
    if not run.traced or run.peaks is None:
        return None
    flops = readers.served_flops(run, run.traced["requests"])
    return 100.0 * flops / (run.traced["window_s"] * run.chips
                            * run.peaks.flops_bf16)
