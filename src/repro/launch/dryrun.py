import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Everything below this line may import jax (device count is locked above).
import argparse
import json
import time
import traceback

import jax

from repro import hw
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.core.ese.records import RooflineRecord
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    HloCost,
    Roofline,
    model_flops_for,
    parse_collectives,
)
from repro.launch.specs import entry_point, input_specs
from repro.models import model

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun.json")


def _cell_key(arch: str, shape: str, mesh: str, tag: str) -> str:
    return f"{arch}|{shape}|{mesh}|{tag}"


def _n_active_matmul(cfg) -> int:
    n = model.count_active_params(cfg)
    if not cfg.tie_embeddings and cfg.family not in ("audio",):
        n -= cfg.vocab_size * cfg.d_model  # embedding gather isn't a matmul
    return n


def run_cell(arch: str, shape_name: str, multi_pod: bool, tag: str = "baseline",
             cfg=None) -> dict:
    """Lower + compile one (arch × shape × mesh) cell; return roofline record."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"

    if not shape_applicable(cfg, shape):
        return {
            "skipped": "long_500k requires sub-quadratic attention "
                       "(full-attention arch; see DESIGN.md §4)",
            "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        }

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    args, shards, donate, out_shards = input_specs(cfg, shape, mesh)
    fn = entry_point(cfg, shape)

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=shards, out_shardings=out_shards,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    hc = HloCost(hlo)
    coll_by_kind = hc.collectives()
    rl = Roofline(
        flops=hc.flops(),
        hbm_bytes=hc.hbm_bytes(),
        collective_bytes=float(sum(coll_by_kind.values())),
        model_flops=model_flops_for(cfg, shape, _n_active_matmul(cfg)),
        chips=chips,
        device_kind=hw.V5E.kind,        # the production mesh is a v5e pod
    )
    # typed round-trip: the ESE record validates the cell at write time,
    # so dryrun.json always matches what RooflineRecord.from_cell expects
    rl_dict = RooflineRecord.from_dict(rl.as_dict()).to_dict()
    peak_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                  + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "config_digest": cfg.digest(),
        "chips": chips,
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": peak_bytes,
            "peak_gib_per_device": round(peak_bytes / 2**30, 3),
        },
        "collectives": {
            "bytes_by_kind": {k: float(v) for k, v in coll_by_kind.items()},
        },
        "cost_analysis_raw": {   # trip-count-unaware; reference only
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": rl_dict,
    }
    return rec


def _load(out_path: str) -> dict:
    if os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    return {}


def _save(out_path: str, results: dict) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, out_path)


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", action="append", choices=list(ARCH_IDS))
    ap.add_argument("--shape", action="append", choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="full sweep")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="config override for hillclimb runs, e.g. "
                         "--set remat_group=4 --set sp_scores_bf16=true")
    ap.add_argument("--out", default=os.path.abspath(DEFAULT_OUT))
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v          # string knob (e.g. layout)

    archs = args.arch or (sorted(ARCH_IDS, key=lambda a: model.count_params(get_config(a)))
                          if args.all else [])
    shapes = args.shape or list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not archs:
        ap.error("pass --arch ... or --all")

    results = _load(args.out)
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        if overrides:
            cfg = cfg.replace(**overrides)
        for shape_name in shapes:
            for mesh_name in meshes:
                key = _cell_key(arch, shape_name, mesh_name, args.tag)
                prev = results.get(key)
                if (prev and not args.force
                        and prev.get("config_digest") == cfg.digest()):
                    print(f"[cached] {key}", flush=True)
                    continue
                print(f"[start ] {key}", flush=True)
                try:
                    rec = run_cell(arch, shape_name, mesh_name == "multi",
                                   args.tag, cfg=cfg)
                    status = ("skipped" if "skipped" in rec else
                              f"ok  compile={rec['t_compile_s']}s "
                              f"dom={rec['roofline']['dominant']} "
                              f"mem={rec['memory']['peak_gib_per_device']}GiB")
                    n_ok += 1
                except Exception as e:  # record failures for triage
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "tag": args.tag, "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-3000:],
                        "config_digest": "FAILED",
                    }
                    status = f"FAIL {type(e).__name__}: {str(e)[:160]}"
                    n_fail += 1
                results[key] = rec
                _save(args.out, results)
                print(f"[done  ] {key}: {status}", flush=True)
    print(f"sweep complete: {n_ok} ok, {n_fail} failed -> {args.out}", flush=True)


if __name__ == "__main__":
    main()
