"""A benchmark directory in a temporary path, for rehearsal tests."""
import json
import shutil

from bench import spec

# rehearsal traffic: fast arrivals, and lengths that survive the
# rehearsal's division by 64 (prompts 8-32 tokens, answers 32-64)
FAST = {"rate_rps": 20.0,
        "prompt": {"dist": "uniform", "min": 512, "max": 2048},
        "output": {"dist": "uniform", "min": 2048, "max": 4096}}


def bench_copy(tmp_path, **traffic_over):
    """Copy BENCHMARK.json and bench/{configs,traffic,loops,references,metrics} into
    ``tmp_path``, each traffic file updated with ``traffic_over``.  Every
    configuration file gets a ``<config>.generate`` cell, with the
    per-layer metrics of the generate cells, so a configuration kept
    without a cell is still rehearsed."""
    for sub in ("configs", "loops", "references", "metrics"):
        shutil.copytree(spec.BENCH_DIR / sub, tmp_path / sub)
    (tmp_path / "traffic").mkdir()
    for f in (spec.BENCH_DIR / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(traffic_over)
        (tmp_path / "traffic" / f.name).write_text(json.dumps(t))
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    gen = {w["name"] for w in bench["workloads"] if w["traffic"] == "generate"}
    for f in sorted((spec.BENCH_DIR / "configs").glob("*.json")):
        name = f.name[: -len(".json")]
        if any(w["config"] == name and w["traffic"] == "generate"
               for w in bench["workloads"]):
            continue
        bench["workloads"].append({"name": f"{name}.generate", "config": name,
                                   "traffic": "generate", "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["per_layer"]:
            if gen & set(m.get("workloads", ())):
                m["workloads"].append(f"{name}.generate")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path / "BENCHMARK.json", tmp_path
