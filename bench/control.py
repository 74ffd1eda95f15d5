#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]
    python bench/control.py --workload <cell> --seeds 1,2,3 --rehearse

For each seed, in one process: weights made from the seed, the cell's
engine (built once; its weights swapped per seed), one group of the
cell's deck served through ``submit`` + ``run`` as in a run's window,
and the same sample a run compares (the longest request and others
drawn from the seed, as many as the configuration's ``check`` block
says).  Prints one JSON line per seed: the widest gap of a served token
below the reference's best (the program's reading) and, for the
control seeds, the widest gap of the token the control ranks first
(the reference the configuration names, ``bench/references/
<name>.py``), each with the sum of the gaps and the number of tokens
off the reference's first choice, and the seconds the reference took
(``reference_s``, the control's pass included on a control seed).
Benchmark runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seeds, control_seeds, rehearse: bool = False,
             bench_json=None, bench_dir=None):
    """Yield {"seed", "program", "control"?, "tokens"} per seed."""
    import numpy as np

    from bench import loadgen, run, spec, weights
    from repro.models import model
    from repro.serve.engine import ServeEngine

    cell = spec.load_cell(workload, bench_json, bench_dir)
    devs = run.device_check(cell.chips, rehearse)
    if not rehearse:
        from repro.launch.cache import use_compile_cache

        use_compile_cache()
    mcfg, m = run.model_config(cell.config, rehearse)
    kw = dict(cell.config["engine"])
    group = kw["max_batch"] + kw["stage_depth"]
    div = run.REHEARSE_DIV if rehearse else 1
    eng = params = None
    for seed in seeds:
        if eng is not None:      # one copy of the weights on the device at a time
            eng.params = params = None
        params = weights.make_params(cell.reference.layout(m), seed)
        weights.check_layout(params, model.abstract_params(mcfg))
        if eng is None:
            eng = ServeEngine(mcfg, params, **kw)
        eng.params = params
        reqs = loadgen.make_group(cell.traffic, 0, group, seed,
                                  m["vocab_size"], None, div)
        served = run.serve(eng, reqs, 0, [0.0] * len(reqs))
        chosen = run.sample(served, cell.config["check"]["sample"], seed)
        ctrl = seed in control_seeds
        gaps = {"program": [], "control": []}
        t0 = time.perf_counter()
        for s in chosen:
            g = cell.reference.gaps(params, m, s.prompt, s.tokens,
                                    kw["kv_frac_kbits"], control=ctrl)
            for k, v in g.items():
                gaps[k].append(v)
        out = {"seed": seed, "device": devs[0].device_kind,
               "tokens": sum(len(s.tokens) for s in chosen),
               "reference_s": time.perf_counter() - t0}
        for k, v in gaps.items():
            if v:
                v = np.concatenate(v)
                out[k] = float(v.max())
                out[k + "_sum"] = float(v.sum())
                out[k + "_flips"] = int((v > 0).sum())
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    seeds = [int(s) for s in a.seeds.split(",")]
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    for r in readings(a.workload, seeds, ctrl, a.rehearse):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
