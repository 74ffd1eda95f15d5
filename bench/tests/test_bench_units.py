"""Unit tests of the benchmark's yardstick: trace reduction, FLOP and
byte counts, the traffic generator, and loading cells by name."""
import hashlib
import json
import math
import re
import shutil
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import counting, loadgen, peaks, spec, tracing
from bench.tests.helpers import bench_copy

TINY = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 2, "d_ff": 16, "vocab_size": 32, "gated_mlp": False,
        "parallel_block": False, "tie_embeddings": False}


# -- trace reduction ----------------------------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def _planes():
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_loop(7)", 100, 500), _ev("jit__prefill_fn(3)", 700, 100)],
        "XLA Ops": [_ev("fusion.1", 100, 200), _ev("paged_attn_kernel", 250, 150),
                    _ev("fusion.2", 400, 200), _ev("paged_attn_kernel", 700, 100)],
    })
    host = _plane("/host:CPU", {"python": [
        _ev("bench.engine_run", 0, 1000), _ev("bench.wait_arrivals", 600, 50)]})
    return [dev, host]


def test_trace_busy_is_union_of_op_intervals():
    s = tracing.reduce_planes(_planes())
    # ops cover [100, 600) and [700, 800): 600 ns busy
    assert s.devices == 1
    assert s.busy_s == pytest.approx(600e-9)


def test_trace_program_and_kernel_time_by_name():
    s = tracing.reduce_planes(_planes())
    assert s.program_s(r"^jit_loop$") == pytest.approx(500e-9)
    assert s.program_s(r"^jit__prefill_fn$") == pytest.approx(100e-9)
    assert s.op_s("paged_attn") == pytest.approx(250e-9)
    assert s.program_s("nothing") is None


def test_trace_idle_gap_named_by_covering_host_event():
    s = tracing.reduce_planes(_planes())
    # the hole [600, 700) lies inside the shorter wait span; the window's
    # edges [0, 100) and [800, 1000) only inside bench.engine_run
    assert s.gaps == [("bench.engine_run", pytest.approx(200e-9)),
                      ("bench.wait_arrivals", pytest.approx(100e-9)),
                      ("bench.engine_run", pytest.approx(100e-9))]


def test_trace_averages_over_devices():
    p = _planes()
    two = [p[0], _plane("/device:TPU:1", {"XLA Ops": [_ev("fusion.1", 0, 200)]}), p[1]]
    s = tracing.reduce_planes(two)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((600e-9 + 200e-9) / 2)


def test_trace_without_device_plane_raises():
    with pytest.raises(ValueError):
        tracing.reduce_planes([_planes()[1]])


def test_union_merges_overlaps():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


# -- counting -----------------------------------------------------------------

def test_layer_params_by_hand():
    # attn: 8*4*2 (q) + 2*8*2*2 (k, v) + 4*2*8 (o) = 64 + 64 + 64; mlp 2*8*16
    assert counting.layer_params(TINY) == 192 + 256
    assert counting.layer_params(dict(TINY, gated_mlp=True)) == 192 + 384


def test_request_flops_by_hand():
    P, n = 3, 4
    lin = 2 * 2 * 448 * (P + n - 1)            # 2 x layers x params x tokens
    head = 2 * 8 * 32 * n                      # once per prompt, per decode token
    slots = (1 + 2 + 3) + (4 + 5 + 6)          # prefill causal + decode contexts
    attn = 4 * 4 * 2 * 2 * slots               # 4 x H x hd x layers per slot
    assert counting.request_flops(TINY, P, n) == lin + head + attn


def test_decode_kv_bytes_by_hand():
    # decode steps read 4, 5, 6 positions; a position is K*hd*2(k,v)*2B*layers
    assert counting.decode_kv_bytes(TINY, 3, 4) == (4 + 5 + 6) * 2 * 2 * 2 * 2 * 2
    assert counting.decode_kv_bytes(TINY, 3, 1) == 0


# -- traffic ------------------------------------------------------------------

CHAT = {"loop": "open", "rate_rps": 2.0,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 128, "max": 2048},
        "output": {"dist": "uniform", "min": 16, "max": 512}}


def test_deck_is_stratified_and_clipped():
    d = loadgen.deck(CHAT["prompt"], 16)
    assert d.min() >= 128 and d.max() == 2048
    assert list(d) == sorted(d)
    assert d[7] < 1024 < d[8]          # the median splits the deck
    u = loadgen.deck(CHAT["output"], 4)
    assert list(u) == [round(16 + (i + 0.5) / 4 * 496) for i in range(4)]


def test_groups_same_schedule_across_seeds_other_tokens():
    a = loadgen.make_group(CHAT, 1, 16, 5, 1000)
    b = loadgen.make_group(CHAT, 1, 16, 5, 1000)
    c = loadgen.make_group(CHAT, 1, 16, 2**33 + 5, 1000)
    d = loadgen.make_group(CHAT, 2, 16, 5, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    # the seed draws the tokens, never the sizes or their order
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    # another group deals the same deck in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in d)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in d]
    assert all(1 <= r.prompt.min() and r.prompt.max() < 1000 for r in a)


def test_arrivals_are_stratified_exponential_gaps_in_a_fixed_order():
    a = loadgen.arrivals(CHAT, 32)
    assert a[0] == 0.0
    assert np.all(np.diff(a) > 0)
    assert np.array_equal(a, loadgen.arrivals(CHAT, 32))
    # the gaps are the exponential's stratified quantiles at rate_rps
    gaps = [-math.log(1 - (i + 0.5) / 32) / 2.0 for i in range(32)]
    assert sum(gaps) - max(gaps) <= a[-1] < sum(gaps)
    assert set(np.round(np.diff(a), 9)) <= set(np.round(gaps, 9))


# -- loading by name ------------------------------------------------------------

def test_real_cells_load_with_their_metrics():
    bench = json.load(open(spec.ROOT / "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_cell_from_files_only_in_a_temporary_directory(tmp_path):
    """A new configuration, mix and metric are new files, nothing else."""
    for sub in ("configs", "traffic", "loops", "references", "metrics"):
        (tmp_path / sub).mkdir()
    shutil.copy(spec.BENCH_DIR / "configs" / "minitron-8b.pp4.json",
                tmp_path / "configs" / "minitron-8b.pp4.json")
    shutil.copy(spec.BENCH_DIR / "references" / "dense.py",
                tmp_path / "references" / "dense.py")
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(
        dict(CHAT, name="burst", loop="bursts")))
    (tmp_path / "loops" / "bursts.py").write_text(
        "def drive(w):\n    w.call(w.requests(0))\n")
    (tmp_path / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    (tmp_path / "metrics" / "setup_s.py").write_text(
        "def read(run):\n    return run.setup_s\n")
    (tmp_path / "B.json").write_text(json.dumps({
        "workloads": [{"name": "m.burst", "config": "minitron-8b.pp4",
                       "traffic": "burst", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "requests_seen", "unit": "count",
                       "better": "higher", "source": "program_counter",
                       "layer": "l", "moves": "setup_s"}]}))
    cell = spec.load_cell("m.burst", tmp_path / "B.json", tmp_path)
    assert cell.traffic["name"] == "burst"
    assert cell.reference.__file__ == str(tmp_path / "references" / "dense.py")
    assert cell.config["check"]["sample"] == 16
    assert [m.name for m in cell.per_layer] == ["requests_seen"]
    assert cell.per_layer[0].read(NS(requests=[1, 2, 3])) == 3
    called = []
    cell.drive(NS(call=called.append, requests=lambda k: k))
    assert called == [0]


def test_unknown_loop_raises(tmp_path):
    bj, bd = bench_copy(tmp_path, loop="no-such-loop")
    with pytest.raises(FileNotFoundError, match="loops"):
        spec.load_cell("stablelm-12b.pp4.generate", bj, bd)


def test_unknown_reference_raises_with_its_path(tmp_path):
    bj, bd = bench_copy(tmp_path)
    path = bd / "configs" / "stablelm-12b.pp4.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    reference="no-such-ref")))
    want = str(bd / "references" / "no-such-ref.py")
    with pytest.raises(FileNotFoundError, match=re.escape(want)):
        spec.load_cell("stablelm-12b.pp4.generate", bj, bd)


# the dense layout of stablelm-12b.pp4 as the harness drew it before the
# reference named it: (path, shape, std), std None for a norm scale
STABLELM_LAYOUT = [
    ("embed", (100352, 5120), 1.0),
    ("final_norm", (5120,), None),
    ("layers/attn_0/wk", (10, 5120, 8, 160), 0.013975424859373685),
    ("layers/attn_0/wo", (10, 32, 160, 5120), 0.0031249999999999997),
    ("layers/attn_0/wq", (10, 5120, 32, 160), 0.013975424859373685),
    ("layers/attn_0/wv", (10, 5120, 8, 160), 0.013975424859373685),
    ("layers/mlp_0/w_down", (10, 13824, 5120), 0.0019018144357818268),
    ("layers/mlp_0/w_gate", (10, 5120, 13824), 0.013975424859373685),
    ("layers/mlp_0/w_up", (10, 5120, 13824), 0.013975424859373685),
    ("layers/norm1_0", (10, 5120), None),
    ("lm_head", (5120, 100352), 0.013975424859373685),
]
# sha256 over (path, bf16 bits) of each leaf, sorted, of the tiny preset's
# weights at seed 4400000719 as the harness drew them before
STABLELM_TINY_SHA256 = \
    "b14f269de583e0ad147a83d2f767474ebf40ef6c9cf60a914fc3b5295326a6d8"


def test_stablelm_dense_layout_and_weights_are_pinned():
    from bench import run, weights

    cell = spec.load_cell("stablelm-12b.pp4.generate")
    assert cell.config["reference"] == "dense"
    got = cell.reference.layout(cell.config["model"])
    assert [(k, *got[k]) for k in sorted(got)] == STABLELM_LAYOUT
    _, m = run.model_config(cell.config, True)
    params = weights.make_params(cell.reference.layout(m), 4400000719)
    h = hashlib.sha256()
    for k, v in sorted(weights.flat(params).items()):
        h.update(k.encode())
        h.update(np.asarray(v).view(np.uint16).tobytes())
    assert h.hexdigest() == STABLELM_TINY_SHA256


def test_model_config_takes_corrected_keys_only_where_listed():
    from bench import run

    cfg = json.load(open(spec.BENCH_DIR / "configs" / "minitron-8b.pp4.json"))
    mcfg, m = run.model_config(cfg, False)
    assert mcfg.num_heads == m["num_heads"] == 48 and mcfg.num_layers == 8
    del cfg["corrected"]
    with pytest.raises(run.BenchError, match="num_heads"):
        run.model_config(cfg, False)
