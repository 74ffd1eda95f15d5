"""Unit tests of ``bench/engine_trace.py``: the engine's spans and the
decode loop's scopes read from look-alike profiler planes, and the
metrics and the result line's traced window that read them."""
from types import SimpleNamespace as NS

import pytest

from bench import engine_trace, spec, tracing


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


LOOP_PATH = "jit(loop)/while/body/closed_call/while/body/"
# (instruction, start, duration, scope path or None for no op_name)
LOOP_OPS = [("fusion.1", 100, 40, "decode.attn/dot_general"),
            ("fusion.2", 140, 10, "decode.attn/kv_write/mul"),
            ("fusion.3", 150, 5, "decode.attn/kv_write/scatter"),
            ("custom-call.4", 155, 45, "decode.attn/attn_read/pallas_call"),
            ("copy.5", 200, 120, None),
            ("dynamic-slice.6", 320, 30, "while/dynamic_slice"),
            ("fusion.7", 350, 60, "decode.mlp/dot_general"),
            ("fusion.8", 410, 20, "decode.head/argmax"),
            ("fusion.9", 430, 10, "loop.alloc/add"),
            ("fusion.10", 440, 10, "loop.emit/scatter"),
            ("fusion.11", 450, 50, "loop.admit/while/body/scatter")]
# by hand: unscoped = the copy and the dynamic-slice; kv_write = 10 + 5
LOOP_TOTAL = 400
BY_HAND = {"": 150, "decode.attn": 40, "decode.attn/kv_write": 15,
           "decode.attn/attn_read": 45, "decode.mlp": 60, "decode.head": 20,
           "loop.alloc": 10, "loop.emit": 10, "loop.admit": 50}


def _span_planes(with_run=True, tpu_names=True):
    """A device that runs the loop [100, 500) and prefill [600, 700)
    inside ``serve.run`` [50, 750), then sits idle for the profiler's
    stop until ``bench.engine_run`` ends at 5000.  Op events are named
    as a TPU names them (``%instr = shape op(...)``) or, with
    ``tpu_names`` false, by the bare instruction (the CPU's form)."""
    def name(instr):
        return f"%{instr} = bf16[8]{{0}} op()" if tpu_names else instr

    ops = [_ev(name(instr), start, dur) for instr, start, dur, _ in LOOP_OPS]
    ops += [_ev(name("while.1"), 100, 400), _ev(name("fusion.20"), 600, 100)]
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_loop(7)", 100, 400),
                        _ev("jit__prefill_fn(3)", 600, 100)],
        "XLA Ops": ops})
    host = [_ev("bench.engine_run", 0, 5000)]
    if with_run:
        host += [_ev("serve.run", 50, 700), _ev("serve.prefill", 60, 20),
                 _ev("serve.loop", 90, 420)]
    return [dev, _plane("/host:CPU", {"python": host})]


def _hlo_ops():
    """The loop's HLO as ``hlo_op_names`` gives it: every instruction
    (those XLA adds with an empty ``op_name``), and prefill's apart."""
    return {instr: ("" if path is None else LOOP_PATH + path)
            for instr, _, _, path in LOOP_OPS + [("while.1", 0, 0, "while")]}


def _read_run(metric, run):
    return spec._function(spec.BENCH_DIR, "metrics", metric, "read")(run)


def _read(metric, engine):
    return _read_run(metric, NS(traced=None if engine is None else {"engine": engine}))


def test_idle_share_run_ignores_the_profilers_stop():
    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=_hlo_ops())
    # busy [100, 500) + [600, 700) of serve.run's [50, 750)
    assert _read("idle_share.run", s) == pytest.approx(100 * (1 - 500 / 700))
    assert s.spans["serve.run"] == [(50, 750)]
    assert s.spans["serve.loop"] == [(90, 510)]
    assert "bench.engine_run" not in s.spans
    # the traced window's own reading counts the tail as idle
    busy_s = tracing.reduce_planes(_span_planes()).busy_s
    assert busy_s == pytest.approx(500e-9)
    assert 100 * (1 - busy_s / 5000e-9) > 85


def test_traced_window_and_mfu_read_the_serve_run_span():
    """``device.window_s`` and ``mfu`` divide by ``serve.run`` [50, 750),
    not by the traced window that runs on to the profiler's stop."""
    from bench import counting, peaks, run

    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=_hlo_ops())
    assert s.span_s("serve.run") == pytest.approx(700e-9)
    assert s.busy_in_s("serve.run") == pytest.approx(500e-9)
    assert s.span_s("serve.spill") is None and s.busy_in_s("serve.spill") is None
    m = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 32, "gated_mlp": False}
    reqs = [NS(prompt=[1] * 3, tokens=[1] * 4), NS(prompt=[1] * 5, tokens=[1] * 2)]
    traced = NS(traced={"engine": s, "requests": reqs}, chips=1, model=m,
                peaks=peaks.peaks("TPU v5 lite"))
    assert run.traced_window(traced) == {"busy_s": pytest.approx(500e-9),
                                         "window_s": pytest.approx(700e-9)}
    flops = counting.request_flops(m, 3, 4) + counting.request_flops(m, 5, 2)
    assert _read_run("mfu", traced) == pytest.approx(
        100 * flops / (700e-9 * 197e12))
    bare = NS(traced={"engine": engine_trace.reduce_engine(
        _span_planes(with_run=False)), "requests": reqs}, chips=1, model=m,
        peaks=traced.peaks)
    assert _read_run("mfu", bare) is None
    with pytest.raises(run.BenchError, match="serve.run"):
        run.traced_window(bare)


def test_span_gaps_named_by_the_engine_step_that_covers_them():
    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=_hlo_ops())
    # holes inside serve.run [50, 750): [50, 100), [500, 600), [700, 750)
    assert s.span_gaps() == [("serve.run", pytest.approx(100e-9)),
                             ("serve.prefill", pytest.approx(50e-9)),
                             ("serve.run", pytest.approx(50e-9))]
    bare = engine_trace.reduce_engine(_span_planes(with_run=False))
    assert bare.span_gaps() == []


@pytest.mark.parametrize("tpu_names", [True, False])
def test_loop_scope_shares_by_hand(tpu_names):
    s = engine_trace.reduce_engine(_span_planes(tpu_names=tpu_names),
                                   hlo_ops=_hlo_ops())
    assert s.loop_scopes == pytest.approx(
        {k: v * 1e-9 for k, v in BY_HAND.items()})
    assert _read("loop_overhead_share", s) == pytest.approx(100 * 150 / LOOP_TOTAL)
    assert _read("kv_write_share", s) == pytest.approx(100 * 15 / LOOP_TOTAL)
    shares = [s.scope_share(k) for k in s.loop_scopes]
    assert sum(shares) == pytest.approx(100.0)
    # the scopes split the loop program's time as the trace reduction has it
    loop_s = tracing.reduce_planes(_span_planes(tpu_names=tpu_names)).program_s(
        r"^jit_loop$")
    assert sum(s.loop_scopes.values()) == pytest.approx(loop_s)


def test_loop_ops_missing_from_the_hlo_count_apart():
    hlo = _hlo_ops()
    del hlo["fusion.7"]
    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=hlo)
    assert s.loop_scopes["?"] == pytest.approx(60e-9)
    assert "decode.mlp" not in s.loop_scopes
    assert sum(s.scope_share(k) for k in s.loop_scopes) == pytest.approx(100.0)


@pytest.mark.parametrize("metric", ["idle_share.run", "loop_overhead_share",
                                    "kv_write_share"])
def test_span_and_scope_metrics_none_without_span_or_scope(metric, monkeypatch):
    # no serve.* span and no scopes (no HLO of the loop) in the trace
    bare = engine_trace.reduce_engine(_span_planes(with_run=False))
    assert bare.spans == {} and bare.loop_scopes == {}
    assert _read(metric, bare) is None
    assert _read(metric, None) is None
    # a program without span and scope names reads nothing either, and
    # its trace is not read again
    monkeypatch.setattr(engine_trace, "SPANS", None)
    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=_hlo_ops())
    assert s.spans == {} and s.loop_scopes == {}
    assert _read(metric, s) is None


def test_ops_without_any_known_scope_read_none():
    hlo = {k: "jit(loop)/while/body/x" for k in _hlo_ops()}
    s = engine_trace.reduce_engine(_span_planes(), hlo_ops=hlo)
    assert s.loop_scopes == {}
    assert _read("loop_overhead_share", s) is None


def test_scope_of_takes_the_innermost_known_scope():
    names = ("decode.attn", "decode.attn/kv_write", "decode.mlp")
    assert engine_trace.scope_of(
        "jit(loop)/while/body/decode.attn/kv_write/mul", names) == "decode.attn/kv_write"
    assert engine_trace.scope_of("jit(loop)/decode.attn/dot", names) == "decode.attn"
    assert engine_trace.scope_of("jit(loop)/kv_write/mul", names) == ""
    assert engine_trace.scope_of("", names) == ""


def test_overlap_of_interval_lists():
    assert engine_trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert engine_trace.overlap([(0, 10)], []) == 0


def test_summary_reads_the_trace_once_per_run(monkeypatch):
    calls = []

    def fake_read(logdir):
        calls.append(logdir)
        return engine_trace.reduce_engine(_span_planes(), hlo_ops=_hlo_ops())

    monkeypatch.setattr(engine_trace, "read_engine_trace", fake_read)
    run = NS(traced={"summary": None})
    first = engine_trace.summary(run)
    assert engine_trace.summary(run) is first and len(calls) == 1
    assert engine_trace.summary(NS(traced=None)) is None
    monkeypatch.setattr(engine_trace, "SPANS", None)
    assert engine_trace.summary(NS(traced={"summary": None})) is None
    assert len(calls) == 1


# -- the loop's HLO from a hand-encoded metadata plane -------------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """Protobuf bytes of (field number, int or bytes or str) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _program(meta_id, name, instrs):
    module = _msg(*[(3, _msg(*[(2, _msg((1, ins), (7, _msg((2, path)))))
                               for ins, path in instrs]))])
    stat = _msg((1, 7), (6, _msg((1, module))))
    return (4, _msg((1, meta_id), (2, _msg((1, meta_id), (2, name), (5, stat)))))


def test_hlo_op_names_from_the_metadata_plane():
    plane = _msg((2, "/host:metadata"),
                 (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
                 _program(3, "jit_loop(3)",
                          [("copy.5", "jit(loop)/while/body/x"),
                           ("fusion.7", "jit(loop)/decode.mlp/dot")]),
                 _program(4, "jit__prefill_fn(4)", [("fusion.9", "jit(p)/y")]))
    other = _msg((2, "/device:TPU:0"), _program(5, "jit_loop(5)",
                                               [("copy.6", "z")]))
    raw = _msg((1, plane), (1, other))
    assert engine_trace.hlo_op_names(raw) == {
        "copy.5": "jit(loop)/while/body/x", "fusion.7": "jit(loop)/decode.mlp/dot"}
    assert engine_trace.hlo_op_names(raw, "jit__prefill_fn") == {
        "fusion.9": "jit(p)/y"}
    assert engine_trace.hlo_op_names(_msg((1, other))) == {}
