"""The harness end to end on the CPU (``--rehearse``: the tiny preset),
the control and the faults its correctness check must catch, the open
loop and a configuration served on a mesh.

Each run goes through the real ``bench/run.py`` path from a benchmark
directory copied into a temporary path (fast arrivals, answers long
enough to compare).  Each fault a serving cell can have -- a token
altered where the engine samples it, a decode step that returns the KV
state unchanged, half of a batch left out -- must turn ``correct``
false; a run without a TPU must fail and print no result.
"""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import run as harness
from bench import spec
from bench.tests.helpers import FAST, bench_copy
from repro.models import model as model_mod
from repro.serve import engine as engine_mod

CELLS = ("stablelm-12b.pp4.generate", "minitron-8b.pp4.generate")


def _run(capsys, tmp_path, cell, seed, *extra, bench=None):
    bj, bd = bench or bench_copy(tmp_path, **FAST)
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "1", "--rehearse", *extra], bj, bd)
    out = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    return line, out.err


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_correct_cpu_line(capsys, tmp_path, cell):
    line, err = _run(capsys, tmp_path, cell, 2**33 + 17)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 32 and line["attempted"] % 16 == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["tokens_compared"]["value"] > 50
    bench = json.load(open(spec.ROOT / "BENCHMARK.json"))
    device_metrics = {m["name"] for m in bench["end_to_end"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(line["metrics"])
    assert "setup_s" in line["metrics"] and "tokens_per_s" in line["metrics"]
    # the numbers compared close stderr, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


def _token_altered(monkeypatch):
    """Every token the engine samples is the one after its argmax."""
    def off_by_one(logits):
        return ((jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1]).astype(jnp.int32)

    monkeypatch.setattr(engine_mod, "greedy_sample", off_by_one)
    return "max_logit_gap"


def _state_unchanged(monkeypatch):
    """Each decode step hands back the KV pool it was given."""
    real = model_mod.decode_step_paged

    def stale(cfg, params, pool, *a, **kw):
        logits, _ = real(cfg, params, pool, *a, **kw)
        return logits, pool

    monkeypatch.setattr(model_mod, "decode_step_paged", stale)
    return "max_logit_gap"


def _half_left_out(monkeypatch):
    """Every other request of a super-bucket comes back empty."""
    real = engine_mod.ServeEngine._finish_bucket

    def drop(self, reqs, out_np, n_np, *a, **kw):
        n_np = n_np.copy()
        n_np[1::2] = 0
        return real(self, reqs, out_np, n_np, *a, **kw)

    monkeypatch.setattr(engine_mod.ServeEngine, "_finish_bucket", drop)
    return "requests_short"


@pytest.mark.parametrize("fault", (_token_altered, _state_unchanged, _half_left_out))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_turns_correct_false(capsys, tmp_path, monkeypatch, cell, fault):
    caught_by = fault(monkeypatch)
    line, _ = _run(capsys, tmp_path, cell, 7)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_turns_correct_false(capsys, tmp_path, cell):
    line, _ = _run(capsys, tmp_path, cell, 2**32 + 3, "--control")
    assert line["correct"] is False
    c = line["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
    assert line["checks"]["tokens_compared"]["value"] > 50


@pytest.mark.parametrize("cell", CELLS)
def test_nothing_compiles_in_the_window(capsys, tmp_path, cell):
    """Set-up's group builds every program the window runs."""
    line, _ = _run(capsys, tmp_path, cell, 5, "--trace", "1")
    assert line["correct"] is True
    assert line["metrics"]["compiles_in_window.offline"]["value"] == 0


def test_open_loop_submits_whatever_is_due(capsys, tmp_path, monkeypatch):
    """An open-loop cell is new files only; each run() gets the requests
    due by then, so the first call serves the first arrival alone."""
    bj, bd = bench_copy(tmp_path, **FAST)
    chat = dict(json.loads((bd / "traffic" / "generate.json").read_text()),
                name="chat", loop="open")
    (bd / "traffic" / "chat.json").write_text(json.dumps(chat))
    bench = json.loads(bj.read_text())
    bench["workloads"].append({"name": "m.chat", "config": "minitron-8b.pp4",
                               "traffic": "chat", "chips": 1, "why": "x"})
    bj.write_text(json.dumps(bench))
    sizes = []
    real = harness.serve

    def spy(eng, reqs, call, dues):
        if call >= 0:
            sizes.append(len(reqs))
        return real(eng, reqs, call, dues)

    monkeypatch.setattr(harness, "serve", spy)
    line, _ = _run(capsys, tmp_path, "m.chat", 9, bench=(bj, bd))
    assert line["correct"] is True and line["failed"] == 0
    assert sum(sizes) == line["attempted"] == 32
    assert sizes[0] == 1 and len(sizes) >= 2


EXTRA_LEAF = """

_dense_layout = layout


def layout(m):
    out = _dense_layout(m)
    out["layers/attn_0/bq"] = ((m["num_layers"], m["num_heads"], m["head_dim"]), 0.02)
    return out
"""


def test_a_reference_is_a_new_file(capsys, tmp_path):
    """A configuration that names a reference brought as a new file in
    ``references/`` runs through ``bench/run.py`` with no other harness
    file changed: a leaf the program lacks (a query bias) is named by
    the layout check before anything is served, and without it the run
    is correct."""
    bj, bd = bench_copy(tmp_path, **FAST)
    dense = (bd / "references" / "dense.py").read_text()
    ref = bd / "references" / "dense_qbias.py"
    ref.write_text(dense + EXTRA_LEAF)
    cfg = json.loads((bd / "configs" / "stablelm-12b.pp4.json").read_text())
    cfg.update(name="stablelm-qbias", reference="dense_qbias")
    (bd / "configs" / "stablelm-qbias.json").write_text(json.dumps(cfg))
    bench = json.loads(bj.read_text())
    bench["workloads"].append({"name": "qbias.generate", "config": "stablelm-qbias",
                               "traffic": "generate", "chips": 1, "why": "x"})
    bj.write_text(json.dumps(bench))
    assert spec.load_cell("qbias.generate", bj, bd).reference.__file__ == str(ref)
    with pytest.raises(ValueError, match="layers/attn_0/bq"):
        harness.main(["--workload", "qbias.generate", "--seed", "3",
                      "--seconds", "1", "--rehearse"], bj, bd)
    assert capsys.readouterr().out == ""
    ref.write_text(dense)
    line, _ = _run(capsys, tmp_path, "qbias.generate", 3, bench=(bj, bd))
    assert line["correct"] is True and line["failed"] == 0


MESH_RUN = """
import sys
from pathlib import Path
from bench import run
sys.exit(run.main(["--workload", "stablelm-12b.pp4.generate", "--seed", "4",
                   "--seconds", "1", "--rehearse"],
                  Path(sys.argv[1]) / "BENCHMARK.json", Path(sys.argv[1])))
"""


def test_a_mesh_cell_is_data_only(tmp_path):
    """Two chips: the configuration's mesh shards the weights as they
    are made and the engine serves on it; no harness code changes."""
    bj, bd = bench_copy(tmp_path, **FAST)
    cfg_path = bd / "configs" / "stablelm-12b.pp4.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["engine"].update(mesh={"data": 1, "model": 2}, paged_kernel=False)
    cfg_path.write_text(json.dumps(cfg))
    bench = json.loads(bj.read_text())
    for w in bench["workloads"]:
        w["chips"] = 2
    bj.write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT), str(spec.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", MESH_RUN, str(bd)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 2


def test_no_tpu_no_result(capsys):
    with pytest.raises(harness.BenchError):
        harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""


def test_checkout_without_the_program_fails(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ exits non-zero
    and prints no result."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
