"""The serving engine's host spans and the paged decode step's device
scopes (``repro/spans.py``), read the way a profiler trace shows them.

On the CPU a profiled ``run()`` writes its ``TraceAnnotation``s on the
host plane's ``python`` line; the scopes are ``op_name`` metadata of
the lowered loop.  Tracing must not change a single token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import engine_trace, tracing
from repro import spans
from repro.configs import get_tiny
from repro.models import model
from repro.models.common import is_leaf_spec
from repro.serve.engine import ServeEngine, build_paged_decode_loop

ARCH = "llama3.2-3b"
PROMPTS = [np.arange(1, 6, dtype=np.int32), np.arange(2, 12, dtype=np.int32),
           np.arange(3, 10, dtype=np.int32), np.arange(4, 11, dtype=np.int32),
           np.arange(5, 14, dtype=np.int32), np.arange(6, 9, dtype=np.int32)]
MAX_NEW = [3, 6, 5, 4, 6, 2]
# max_batch 2 + stage_depth 2: the six requests take two super-buckets
ENGINE = dict(max_batch=2, paged=True, page_size=4, stage_depth=2,
              kv_frac_kbits=8)
BUCKET_STEPS = [spans.ADMIT, spans.PREFILL, spans.POOL_FILL,
                spans.FIRST_TOKEN_SYNC, spans.LOOP, spans.FINISH]


@pytest.fixture(scope="module")
def params():
    return model.init_params(get_tiny(ARCH), jax.random.PRNGKey(0))


def _serve(params, logdir=None, **kw):
    eng = ServeEngine(get_tiny(ARCH), params, **dict(ENGINE, **kw))
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, MAX_NEW)]
    if logdir is None:
        res = eng.run()
    else:
        with jax.profiler.trace(str(logdir)):
            res = eng.run()
    return eng, [res[r] for r in rids]


def _python_spans(logdir):
    """serve.* events of the host plane's python line, by start time."""
    pd = ProfileData.from_file(tracing.find_xplane(logdir))
    host = next(pl for pl in pd.planes if pl.name == "/host:CPU")
    line = next(ln for ln in host.lines if ln.name == "python")
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in line.events if ev.name in spans.HOST)


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """One profiled run(), after an untraced one compiled every program."""
    _serve(params)
    logdir = tmp_path_factory.mktemp("trace")
    eng, out = _serve(params, logdir)
    return eng, out, logdir


def test_run_emits_serve_run_once_around_the_bucket_steps(traced):
    eng, _, logdir = traced
    evs = _python_spans(logdir)
    runs = [(s, e) for s, e, n in evs if n == spans.RUN]
    assert len(runs) == 1
    (r0, r1), = runs
    steps = [(s, e, n) for s, e, n in evs if n != spans.RUN]
    assert all(r0 <= s and e <= r1 for s, e, _ in steps)
    # the six steps, in order, once per super-bucket, none overlapping
    assert eng.stats.prefills == 2
    assert [n for _, _, n in steps] == BUCKET_STEPS * 2
    assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))


def test_span_arguments_are_scalars_on_the_event(traced):
    _, _, logdir = traced
    pd = ProfileData.from_file(tracing.find_xplane(logdir))
    host = next(pl for pl in pd.planes if pl.name == "/host:CPU")
    line = next(ln for ln in host.lines if ln.name == "python")
    stats = {ev.name: dict(ev.stats) for ev in line.events
             if ev.name in (spans.RUN, spans.PREFILL)}
    assert stats[spans.RUN]["R"] == len(PROMPTS)
    assert {"R", "S"} <= set(stats[spans.PREFILL])


def test_trace_holds_the_loops_hlo_with_its_scopes(traced):
    """The trace's own copy of the loop's HLO, which the reduction reads
    the scopes from, names every device scope."""
    _, _, logdir = traced
    raw = open(tracing.find_xplane(logdir), "rb").read()
    names = engine_trace.hlo_op_names(raw)
    found = {engine_trace.scope_of(p, spans.DEVICE) for p in names.values()}
    assert set(spans.DEVICE) <= found
    assert "" in found                 # XLA's own instructions carry none


def test_outputs_token_identical_with_and_without_a_trace(traced, params):
    _, out, _ = traced
    _, plain = _serve(params)
    assert out == plain


def test_flash_waves_add_spill_and_fault_in(params, tmp_path):
    from repro.core.frac.wear import RecycledChip
    from repro.serve.faults import FaultConfig
    from repro.serve.flash_tier import FlashTier

    def tier():
        return FlashTier(RecycledChip(n_blocks=64, seed=1),
                         faults=FaultConfig(seed=1, rber_scale=0.0))

    _, plain = _serve(params, stage_depth=8, flash=tier())
    eng, out = _serve(params, tmp_path, stage_depth=8, flash=tier())
    assert out == plain and eng.stats.oversub_waves >= 2
    names = [n for _, _, n in _python_spans(tmp_path)]
    assert names.count(spans.RUN) == 1
    assert names.count(spans.SPILL) == 1
    assert names.count(spans.FAULT_IN) == eng.stats.oversub_waves - 1
    assert names.count(spans.LOOP) == eng.stats.oversub_waves


def test_contiguous_bucket_shares_the_step_names(params, tmp_path):
    eng, _ = _serve(params, tmp_path, paged=False)
    names = [n for _, _, n in _python_spans(tmp_path) if n != spans.RUN]
    assert names == [spans.ADMIT, spans.PREFILL, spans.FIRST_TOKEN_SYNC,
                     spans.LOOP, spans.FINISH] * eng.stats.prefills


@pytest.mark.parametrize("kv_kbits", [None, 8])
def test_lowered_paged_loop_carries_the_decode_scopes(kv_kbits):
    cfg = get_tiny(ARCH)
    B, Q, mp, P, ps = 2, 2, 4, 16, 4
    pool = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        model.paged_pool_specs(cfg, P, ps),
                        is_leaf=is_leaf_spec)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    loop = build_paged_decode_loop(cfg, kv_kbits=kv_kbits, out_cap=8,
                                   page_size=ps)
    text = loop.lower(model.abstract_params(cfg), pool, i32(B, mp), i32(P),
                      i32(), i32(B), i32(B), i32(Q), i32(Q), i32(Q, mp),
                      i32(B + Q)).as_text(debug_info=True)
    for scope in (spans.KV_WRITE_PATH, spans.ATTN_READ_PATH, spans.MLP,
                  spans.HEAD, spans.LOOP_ALLOC, spans.LOOP_EMIT,
                  spans.LOOP_ADMIT):
        assert scope in text, scope
