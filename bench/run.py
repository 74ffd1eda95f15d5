#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --workload <cell> --seed <n> --seconds <s> --rehearse

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``); the mix
names its loop (``bench/loops/<loop>.py``).  Set-up makes the weights
on the device from the seed (sharded over the configuration's mesh,
where it has one), builds the program's ``ServeEngine`` with the
configuration's engine settings and serves one group of the mix's
deck, which compiles every program the window runs (``setup_s`` ends
at the window's first submission).  The loop then drives
``ServeEngine.submit`` + ``ServeEngine.run`` through the window.

Every run makes at least two ``run`` calls.  With ``--trace 1`` the
second is profiled (with any wait for arrivals before it) and the
per-layer metrics are read from that trace; with ``--trace 0`` the
end-to-end metrics are printed.  After the window, the engine is freed
and a seeded sample of the served requests (as many as the
configuration's ``check`` block says), the longest among them, is
compared with the float32 reference the configuration names
(``bench/references/<name>.py``, which also gives the weights'
layout); the numbers compared are printed beside their limits on
stderr and under ``checks``, the last key of the result, which is the
last line of stdout.  ``--control`` compares the reference's own
choices one precision step down (fp8 weights, a 4-bit KV tier) in
place of the served tokens: the check must then read false.  Benchmark
runs never take it.

Without a TPU (or with fewer chips than the cell asks for) the run
fails and prints no result.  ``--rehearse`` is the one exception, for
tests and review on a CPU: JAX on the CPU, the repository's tiny
preset of the configuration, lengths divided by 64, no trace and no
device metric; its line says ``"platform": "cpu"``.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
REHEARSE_DIV = 64          # CPU rehearsal: lengths divided by this
TRACE_DIR = ROOT / ".bench_trace"
WARM_GROUP = 2**31 - 1     # the set-up group's stream (window groups are 0, 1, ...)
TRACED_CALL = 1            # the run() call that --trace 1 profiles
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a bad file)."""


@dataclass
class Served:
    """One request of the window, as the client saw it."""
    index: int
    call: int               # the run() call that served it (0, 1, ...)
    due: float              # absolute (perf_counter) due time
    start: float            # the run() that served it began
    done: float             # run() returned: the client holds its tokens
    prompt: object          # np.ndarray
    max_new: int
    tokens: list[int] = field(default_factory=list)


@dataclass
class Run:
    """What metric readers see (``bench/metrics/<name>.py``)."""
    cell: str
    model: dict
    engine: dict
    chips: int
    platform: str
    peaks: object | None            # bench.peaks.Peaks, None off the chip
    setup_s: float
    window_s: float
    requests: list[Served]
    stats: dict                     # ServeStats deltas over the window
    compiles: int                   # programs built inside the window
    traced: dict | None = None      # {"stats", "requests", "summary", "engine"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny preset (no chip)")
    ap.add_argument("--control", action="store_true",
                    help="score the lower-precision control, not the program")
    return ap.parse_args(argv)


def model_config(config: dict, rehearse: bool):
    """The program's ModelConfig for this configuration, after checking
    that the repository's config has the file's sizes, except for the
    keys the file lists under ``reduced`` (cut to fit) or ``corrected``
    (where the repository's file departs from the published model)."""
    from repro.configs import get_config, get_tiny

    base = get_config(config["repo_config"])
    m = dict(config["model"])
    own = set(config["reduced"]) | set(config.get("corrected", {}))
    for k, v in m.items():
        if k not in own and getattr(base, k) != v:
            raise BenchError(f"{config['name']}: repo config "
                             f"{config['repo_config']!r} has {k}="
                             f"{getattr(base, k)!r}, the file {v!r}")
    mcfg = base.replace(**m)
    if rehearse:
        mcfg = get_tiny(config["repo_config"])
        m = {k: getattr(mcfg, k) for k in m}
    return mcfg, m


def device_check(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def make_mesh(engine: dict, chips: int):
    """The configuration's (data, model) mesh, or None on one chip."""
    shape = engine.get("mesh")
    if shape is None:
        if chips != 1:
            raise BenchError(f"{chips} chips but no mesh in the configuration")
        return None
    if shape["data"] * shape["model"] != chips:
        raise BenchError(f"mesh {shape} does not cover {chips} chips")
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=shape["data"], model=shape["model"])


class CompileCounter:
    """Counts programs built (XLA compiles and persistent-cache loads)
    while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.n += 1

    def _ev(self, event, **kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.n += 1


STAT_KEYS = ("requests", "tokens", "prefills", "decode_steps",
             "host_syncs", "admissions")


def _stats(eng) -> dict:
    return {k: getattr(eng.stats, k) for k in STAT_KEYS}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def serve(eng, reqs, call: int, dues) -> list[Served]:
    """Submit ``reqs`` (due at the absolute times ``dues``), run the
    engine until it has served them, and return them as served."""
    t0 = time.perf_counter()
    rids = [eng.submit(r.prompt, max_new_tokens=r.max_new) for r in reqs]
    out = eng.run()
    t1 = time.perf_counter()
    return [Served(r.index, call, due, t0, t1, r.prompt, r.max_new,
                   list(out[rid])) for r, rid, due in zip(reqs, rids, dues)]


class Window:
    """What a loop (``bench/loops/<loop>.py``) drives: the engine, the
    mix and the window's clock.  ``call`` serves requests through one
    ``run``; ``wait_until`` sleeps for arrivals.  The ``TRACED_CALL``-th
    call is profiled, from any wait before it to its end."""

    def __init__(self, eng, traffic, group, seed, seconds, vocab, div,
                 trace: bool):
        self.eng, self.traffic, self.group = eng, traffic, group
        self.seed, self.seconds, self.vocab, self.div = seed, seconds, vocab, div
        self.trace = trace
        self.served: list[Served] = []
        self.calls = 0
        self.traced = None
        self._s0 = None                 # stats as the traced call began
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def requests(self, k: int, due=None):
        """Group ``k`` of the mix; ``due`` (s after the window opens)
        indexed by request, or None where requests are due on submission."""
        from bench import loadgen

        return loadgen.make_group(self.traffic, k, self.group, self.seed,
                                  self.vocab, due, self.div)

    def _begin(self):
        if self.trace and self.calls == TRACED_CALL and self._s0 is None:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._s0 = _stats(self.eng)

    def wait_until(self, due_s: float) -> None:
        self._begin()
        wait = self.t0 + due_s - time.perf_counter()
        if wait > 0:
            import jax

            with jax.profiler.TraceAnnotation("bench.wait_arrivals"):
                time.sleep(wait)

    def call(self, reqs, due_s=None) -> None:
        """Serve ``reqs`` through one ``run``; each is due ``due_s``
        after the window opened, or on submission where that is None."""
        import jax

        self._begin()
        now = time.perf_counter()
        dues = ([now] * len(reqs) if due_s is None
                else [self.t0 + d for d in due_s])
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            got = serve(self.eng, reqs, self.calls, dues)
        self.served += got
        if self._s0 is not None and self.traced is None:
            jax.profiler.stop_trace()
            self.traced = {"stats": _delta(self._s0, _stats(self.eng)),
                           "requests": got}
        self.calls += 1


def sample(served: list[Served], k: int, seed: int) -> list[Served]:
    """The longest request (prompt + output) and ``k - 1`` others drawn
    from the seed."""
    import numpy as np

    done = [s for s in served if s.tokens]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.prompt) + len(s.tokens), -s.index))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed & (2**63 - 1), 3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check(reference, params, m: dict, kv_bits: int, chosen, limit: float,
          control: bool = False) -> dict:
    """Numbers compared, each beside its limit, by ``reference`` (the
    configuration's reference module).  With ``control`` the gaps are
    those of the tokens the control ranks first."""
    import numpy as np

    who = "control" if control else "program"
    worst = 0.0
    n_tok = 0
    for s in chosen:
        g = reference.gaps(params, m, s.prompt, s.tokens, kv_bits,
                           control=control)[who]
        worst = max(worst, float(np.max(g)))
        n_tok += len(g)
    return {"max_logit_gap": {"value": worst, "limit": limit},
            "tokens_compared": {"value": n_tok, "limit": None}}


def traced_window(run) -> dict:
    """``busy_s`` and ``window_s`` of the traced call: the device's op
    time inside its ``serve.run`` span and the span's length, on the
    trace's clock, so the profiler's start and stop are no part of it."""
    from bench import engine_trace, readers

    window_s = readers.traced_run_s(run)
    if window_s is None:
        raise BenchError("the trace holds no serve.run span of the engine")
    busy_s = engine_trace.summary(run).busy_in_s(engine_trace.SPANS.RUN)
    return {"busy_s": busy_s, "window_s": window_s}


def device_block(devs) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def main(argv=None, bench_json=None, bench_dir=None) -> int:
    """``bench_json`` / ``bench_dir`` point the loader elsewhere (tests)."""
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import spec

    cell = spec.load_cell(args.workload, bench_json, bench_dir)
    config, traffic = cell.config, cell.traffic
    import jax

    devs = device_check(cell.chips, args.rehearse)
    from bench.peaks import peaks

    pk = None if args.rehearse else peaks(devs[0].device_kind)
    if not args.rehearse:
        from repro.launch.cache import use_compile_cache

        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    from bench import weights
    from repro.models import model
    from repro.serve.engine import ServeEngine

    mcfg, m = model_config(config, args.rehearse)
    eng_kw = dict(config["engine"])
    mesh = make_mesh(eng_kw, cell.chips)
    eng_kw.pop("mesh", None)
    group = eng_kw["max_batch"] + eng_kw["stage_depth"]
    div = REHEARSE_DIV if args.rehearse else 1
    shardings = None
    if mesh is not None:
        from repro.sharding import rules

        shardings = rules.param_shardings(model.param_specs(mcfg), mesh)
    params = weights.make_params(cell.reference.layout(m), args.seed, shardings)
    weights.check_layout(params, model.abstract_params(mcfg))
    eng = ServeEngine(mcfg, params, mesh=mesh, **eng_kw)
    # set-up: one group of the deck builds every program the window runs
    from bench import loadgen

    serve(eng, loadgen.make_group(traffic, WARM_GROUP, group, args.seed,
                                  m["vocab_size"], None, div), -1, [0.0] * group)
    jax.effects_barrier()
    setup_s = time.time() - T_PROCESS

    s0 = _stats(eng)
    win = Window(eng, traffic, group, args.seed, args.seconds,
                 m["vocab_size"], div, bool(args.trace) and not args.rehearse)
    counter.on = True
    cell.drive(win)
    counter.on = False
    window_s = win.elapsed()
    served, traced = win.served, win.traced
    stats = _delta(s0, _stats(eng))
    device = device_block(devs)
    if traced is not None:
        from bench.tracing import read_trace

        traced["summary"] = read_trace(TRACE_DIR)
    del eng, win
    gc.collect()

    run = Run(cell=cell.name, model=m, engine=eng_kw, chips=cell.chips,
              platform=devs[0].platform, peaks=pk, setup_s=setup_s,
              window_s=window_s, requests=served, stats=stats,
              compiles=counter.n, traced=traced)
    if traced is not None:
        device.update(traced_window(run))
    metrics = {}
    for met in (cell.per_layer if args.trace else cell.end_to_end):
        if args.rehearse and met.source == "device_trace":
            continue
        v = met.read(run)
        if v is not None:
            metrics[met.name] = {"value": v, "unit": met.unit}

    chk = config["check"]
    limit = chk["rehearse_max_logit_gap"] if args.rehearse else chk["max_logit_gap"]
    short = sum(len(s.tokens) != s.max_new for s in served)
    checks = check(cell.reference, params, m, eng_kw["kv_frac_kbits"],
                   sample(served, chk["sample"], args.seed), limit, args.control)
    checks["requests_short"] = {"value": short, "limit": 0}
    correct = all(c["limit"] is None or c["value"] <= c["limit"]
                  for c in checks.values())
    line = {"correct": correct, "attempted": len(served), "failed": short,
            "metrics": metrics, "device": device}
    if traced is not None:
        summ = traced["summary"]
        line["breakdown"] = {
            "device_ops": sorted(summ.ops.items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": summ.gaps[:10]}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
