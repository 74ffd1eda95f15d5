"""The ``step3.ep6.generate`` cell on the CPU (``--rehearse``: the tiny
preset, 8 of 48 experts held), its control and the faults its check
must catch, and the ``moe_share`` reader on a look-alike trace.

The faults are those of ``test_bench_rehearse.py``, imported from it:
a token altered where the engine samples it, a decode step that
returns the KV state unchanged, half of a super-bucket left out.
"""
import json
from types import SimpleNamespace as NS

import pytest

from bench import control, engine_trace, spec
from bench import run as harness
from bench.tests.helpers import FAST, bench_copy
from bench.tests.test_bench_rehearse import (_half_left_out, _state_unchanged,
                                             _token_altered)

CELL = "step3.ep6.generate"


def _group(bd) -> int:
    eng = spec.load_cell(CELL, bd / "BENCHMARK.json", bd).config["engine"]
    return eng["max_batch"] + eng["stage_depth"]


def _run(capsys, tmp_path, seed, *extra):
    bj, bd = bench_copy(tmp_path, **FAST)
    rc = harness.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "1", "--rehearse", *extra], bj, bd)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), _group(bd)


def test_rehearsal_prints_a_correct_cpu_line(capsys, tmp_path):
    line, group = _run(capsys, tmp_path, 2**33 + 17)
    assert line["correct"] is True and line["failed"] == 0
    # whole super-bucket groups, at least two: set-up's group is apart
    assert line["attempted"] >= 2 * group and line["attempted"] % group == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["tokens_compared"]["value"] > 200
    assert {"setup_s", "tokens_per_s"} <= set(line["metrics"])


@pytest.mark.parametrize("fault", (_token_altered, _state_unchanged, _half_left_out))
def test_fault_turns_correct_false(capsys, tmp_path, monkeypatch, fault):
    caught_by = fault(monkeypatch)
    line, _ = _run(capsys, tmp_path, 7)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] > c["limit"]


def test_control_in_the_programs_place_turns_correct_false(capsys, tmp_path):
    line, _ = _run(capsys, tmp_path, 2**32 + 3, "--control")
    assert line["correct"] is False
    c = line["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_control_fails_where_the_program_passes(tmp_path):
    bj, bd = bench_copy(tmp_path, **FAST)
    limit = spec.load_cell(CELL, bj, bd).config["check"]["rehearse_max_logit_gap"]
    seeds = (11, 12, 13)
    got = list(control.readings(CELL, seeds, set(seeds), True, bj, bd))
    assert len(got) == 3
    for r in got:
        assert r["tokens"] > 200
        assert r["program"] <= limit < r["control"], r


def test_nothing_compiles_in_the_window(capsys, tmp_path):
    line, _ = _run(capsys, tmp_path, 5, "--trace", "1")
    assert line["correct"] is True
    assert line["metrics"]["compiles_in_window.offline"]["value"] == 0


# -- moe_share on look-alike profiler planes ------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


LOOP_PATH = "jit(loop)/while/body/closed_call/while/body/"
# (instruction, start, duration, op_name: a scope path under LOOP_PATH,
# or None for none)
LOOP_OPS = [("fusion.1", 100, 40, "decode.attn/dot_general"),
            ("fusion.2", 140, 10, "decode.attn/kv_write/scatter"),
            ("custom-call.3", 150, 50, "decode.attn/attn_read/pallas_call"),
            ("fusion.4", 200, 10, "decode.moe/mul"),
            ("fusion.5", 210, 15, "decode.moe/route/top_k"),
            ("fusion.6", 225, 95, "decode.moe/experts/dot_general"),
            ("fusion.8", 320, 40, "decode.moe/shared/dot_general"),
            ("copy.9", 360, 20, None),
            ("fusion.10", 380, 20, "decode.head/argmax")]
LOOP_TOTAL = 300                 # 100 .. 400
MOE_TIME = 10 + 15 + 95 + 40


def _planes():
    ops = [_ev(f"%{instr} = bf16[8]{{0}} op()", start, dur)
           for instr, start, dur, _ in LOOP_OPS]
    ops += [_ev("%while.1 = bf16[8]{0} op()", 100, 300)]
    dev = _plane("/device:TPU:0", {"XLA Modules": [_ev("jit_loop(7)", 100, 300)],
                                   "XLA Ops": ops})
    host = _plane("/host:CPU", {"python": [_ev("serve.run", 50, 400)]})
    return [dev, host]


def _hlo_ops(ops=LOOP_OPS):
    return {instr: "" if path is None else LOOP_PATH + path
            for instr, _, _, path in ops + [("while.1", 0, 0, "while")]}


def _moe_share():
    return spec._module(spec.BENCH_DIR, "metrics", "moe_share", "read")


def _traced_run(monkeypatch, hlo_ops):
    """A traced run whose engine reading comes from the planes above;
    returns the run and the list of trace reads made."""
    reads = []

    def fake_read(logdir):
        reads.append(logdir)
        return engine_trace.reduce_engine(_planes(), hlo_ops=hlo_ops)

    monkeypatch.setattr(engine_trace, "read_engine_trace", fake_read)
    return NS(traced={"summary": None}), reads


def test_moe_share_reads_decode_moe_and_its_children(monkeypatch):
    run, _ = _traced_run(monkeypatch, _hlo_ops())
    s = engine_trace.summary(run)
    assert set(s.loop_scopes) >= {"decode.moe", "decode.moe/route",
                                  "decode.moe/experts", "decode.moe/shared"}
    assert _moe_share().read(run) == pytest.approx(100 * MOE_TIME / LOOP_TOTAL)
    # the MoE's ops are not the loop's overhead: only the copy is
    assert s.scope_share("") == pytest.approx(100 * 20 / LOOP_TOTAL)


def test_moe_share_reads_the_trace_once_per_run(monkeypatch):
    run, reads = _traced_run(monkeypatch, _hlo_ops())
    mod = _moe_share()
    assert mod.read(run) == pytest.approx(100 * MOE_TIME / LOOP_TOTAL)
    assert mod.read(run) == pytest.approx(100 * MOE_TIME / LOOP_TOTAL)
    assert len(reads) == 1
    assert mod.read(NS(traced=None)) is None and len(reads) == 1


def test_moe_share_none_without_the_scope(monkeypatch):
    dense = [op for op in LOOP_OPS if "decode.moe" not in (op[3] or "")]
    run, _ = _traced_run(monkeypatch, _hlo_ops(dense))
    assert _moe_share().read(run) is None
    # a program whose span names have no MoE scope (the parent's) reads
    # nothing, raises nothing and does not read the trace
    run, reads = _traced_run(monkeypatch, _hlo_ops())
    monkeypatch.setattr(engine_trace, "SPANS", NS(
        **{k: getattr(engine_trace.SPANS, k) for k in ("HOST", "RUN")},
        DEVICE=("decode.attn", "decode.head")))
    assert _moe_share().read(run) is None and reads == []
