"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows.  Mapping to the paper:

  bench_frac             Fig 2(c), Fig 2(d), Fig 6, codec throughput
  bench_frac_capacity    Fig 2(d) lifetime: m-ladder vs MLC->SLC cliff
  bench_progress_carbon  Fig 5 right (forward progress), Fig 5 left (Pareto)
  bench_ese_wind         Fig 7 (LSTM wind prediction)
  bench_kernels          §II-A NTT / SHA3 workloads
  bench_roofline         EXPERIMENTS §Roofline table (from the dry-run)
  bench_ese_estimates    Fig 4(a) estimator pipeline end-to-end
  bench_serve            serving decode tokens/s + J/token (device-
                         resident while_loop vs seed per-token sync;
                         paged long-context decode kernel-vs-gather
                         tokens/s + attention-transient bytes)
  bench_fleet            multi-region fleet replay: router-policy
                         SLO-vs-gCO2/token Pareto + schema/identity gates
  bench_reconfig         §II-A AMOEBA reconfiguration: per-interval
                         config selection vs binary RUN/DERATE/PAUSE

Usage:
  python benchmarks/run.py [--sections frac,kernels] [--json [DIR]]

``--sections`` runs a comma-separated subset (CI smoke checks run just
``frac,kernels``).  ``--json`` additionally writes one
``BENCH_<section>.json`` per section — rows plus wall seconds — so the
perf trajectory is machine-readable across commits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="write BENCH_<section>.json files into DIR")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_chaos,
        bench_ese_estimates,
        bench_ese_wind,
        bench_fleet,
        bench_frac,
        bench_frac_capacity,
        bench_kernels,
        bench_progress_carbon,
        bench_reconfig,
        bench_roofline,
        bench_serve,
    )

    modules = [
        ("frac", bench_frac),
        ("frac_capacity", bench_frac_capacity),
        ("progress_carbon", bench_progress_carbon),
        ("ese_wind", bench_ese_wind),
        ("kernels", bench_kernels),
        ("roofline", bench_roofline),
        ("ese_estimates", bench_ese_estimates),
        ("serve", bench_serve),
        ("fleet", bench_fleet),
        ("chaos", bench_chaos),
        ("reconfig", bench_reconfig),
    ]
    if args.sections:
        wanted = {s.strip() for s in args.sections.split(",") if s.strip()}
        unknown = wanted - {n for n, _ in modules}
        if unknown:
            sys.exit(f"unknown sections: {sorted(unknown)} "
                     f"(have {[n for n, _ in modules]})")
        modules = [(n, m) for n, m in modules if n in wanted]

    print("name,value,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        rows: list[dict] = []
        error: str | None = None
        try:
            for row in mod.run():
                n, v, d = row
                print(f"{n},{v:.6g},{d}")
                rows.append({"name": n, "value": float(v), "derived": d})
        except Exception as e:  # keep the harness running
            failures += 1
            error = f"{type(e).__name__}: {e}"
            print(f"{name}_FAILED,0,{error}")
        wall = time.time() - t0
        print(f"_section_{name}_seconds,{wall:.1f},wall", flush=True)
        if args.json is not None:
            os.makedirs(args.json, exist_ok=True)
            out = {"section": name, "rows": rows, "seconds": round(wall, 3)}
            if error is not None:
                out["error"] = error
            path = os.path.join(args.json, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    main()
