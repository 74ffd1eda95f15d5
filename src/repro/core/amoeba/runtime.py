"""AMOEBA reconfiguration runtime (paper §II-A, made a runtime behavior).

Each scheduler interval, a ``ReconfigController`` searches the typed
``HwConfig`` space (configspace.py) for the highest-utility
configuration whose modeled power draw fits the renewable budget —
replacing the binary RUN/DERATE/PAUSE ladder of
``CarbonAwareScheduler`` with a real configuration search.  Consumers:

  - ``train/loop.py`` executes each step at the chosen config's FRAC
    grad-compress width (derating steps *down the compression ladder*
    before it slows the step rate);
  - ``serve/fleet.py`` regions derate via the chosen config's bucket
    width and run fill primitives between serve waves;
  - ``SustainabilityMeter`` books every decision's power scale and
    attributes avoided energy + fill work per config
    (``EnergyReport.detail["reconfig"]``).

The seed NTT/SHA3 kernels become *schedulable fill primitives*: a
``PrimitiveJob`` queue the controller dispatches into intervals whose
budget can't fit model work (``run_primitive`` executes them for real
on the same substrate, via ``engines.dispatch``) — GreenFPGA's
reconfigurability-amortizes-embodied-carbon argument, executable.

``replay_supply`` replays a supply/intensity trace through either
decider with identical metering, yielding the progress-per-total-kgCO2
comparison ``benchmarks/bench_reconfig.py`` sweeps and CI gates.
"""
from __future__ import annotations

import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Iterable

import numpy as np

from repro.core.amoeba import engines
from repro.core.amoeba.configspace import (
    ConfigSpace,
    CostModel,
    HwConfig,
    train_space,
)
from repro.core.power import traces
from repro.core.power.scheduler import Action, Decision, resolve_forecast

INTERVAL_S = traces.STEP_MIN * 60.0

_EPS = 1e-9


# ---------------------------------------------------------------------------
# Schedulable fill primitives (the paper's intensive computing primitives)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PrimitiveJob:
    """One schedulable unit of non-model work for the substrate."""
    workload: str                  # engines.dispatch key: ntt / sha3 / conv
    size: int = 256                # problem scale (points / messages / rows)
    seed: int = 0

    def __post_init__(self):
        engines.dispatch(self.workload)     # validates the workload name
        if self.size < 1:
            raise ValueError(
                f"PrimitiveJob: size must be >= 1, got {self.size}")


@dataclass(frozen=True)
class PrimitiveResult:
    job: PrimitiveJob
    engines: tuple                 # PE set the dispatch mapped it to
    wall_s: float
    work_units: float              # workload-native op count
    checksum: int                  # result digest (determinism witness)


def run_primitive(job: PrimitiveJob) -> PrimitiveResult:
    """Actually execute a fill primitive on the substrate the serve /
    train job runs on.  Deterministic per (workload, size, seed): the
    checksum witnesses that a dispatched job computed the same result
    wherever the controller scheduled it."""
    import jax

    pes = engines.dispatch(job.workload)
    rng = np.random.default_rng(job.seed)
    # the Pallas kernels compile for a TPU; any other backend can only
    # run them through the interpreter
    interpret = jax.default_backend() != "tpu"
    t0 = time.perf_counter()
    if job.workload == "ntt":
        from repro.kernels.ntt import ops as ntt_ops
        from repro.kernels.ntt import ref as ntt_ref
        n = 1 << max(int(np.log2(max(job.size, 2))), 1)
        a = rng.integers(0, ntt_ref.Q, (2, n)).astype(np.int32)
        b = rng.integers(0, ntt_ref.Q, (2, n)).astype(np.int32)
        out = np.asarray(ntt_ops.negacyclic_mul(a, b, interpret=interpret))
        work = float(2 * n * max(np.log2(n), 1.0))
        digest = zlib.crc32(out.tobytes())
    elif job.workload == "sha3":
        from repro.kernels.sha3 import ops as sha3_ops
        msgs = [rng.integers(0, 256, 64).astype(np.uint8).tobytes()
                for _ in range(job.size)]
        digests = sha3_ops.sha3_256(msgs, interpret=interpret)
        work = float(sum(len(m) for m in msgs))
        digest = zlib.crc32(b"".join(digests))
    else:                                   # "conv": pure MPE MVM
        import jax.numpy as jnp
        x = jnp.asarray(rng.standard_normal((job.size, job.size)),
                        jnp.float32)
        w = jnp.asarray(rng.standard_normal((job.size, job.size)),
                        jnp.float32)
        out = np.asarray(engines.mpe_mvm(x, w))
        work = float(2 * job.size ** 3)
        digest = zlib.crc32(np.ascontiguousarray(out).tobytes())
    wall = time.perf_counter() - t0
    return PrimitiveResult(job=job, engines=pes, wall_s=wall,
                           work_units=work, checksum=int(digest))


# ---------------------------------------------------------------------------
# The controller
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ReconfigDecision:
    """One interval's chosen configuration + the budget it had to fit."""
    config: HwConfig
    power_frac: float              # modeled draw of the chosen config
    utility: float                 # modeled useful progress this interval
    budget_frac: float             # renewable budget the search fit

    @property
    def step_scale(self) -> float:
        """Legacy-Decision-compatible rate dial (the train loop's pause
        check and the meter's fallback read this)."""
        return self.config.step_scale

    @property
    def action(self) -> Action:
        """Binary-ladder interop: what the PAUSE/DERATE ladder would
        call this config."""
        if self.config.step_scale == 0.0 and self.config.bucket_frac == 0.0:
            return Action.PAUSE
        if self.utility >= 1.0 - _EPS:
            return Action.RUN
        return Action.DERATE

    def as_decision(self) -> Decision:
        return Decision(self.action, float(self.config.step_scale),
                        int(self.config.grad_kbits))


class ReconfigController:
    """Per-interval hardware-config selection under a renewable budget.

    ``decide`` picks the feasible (``power_frac(cfg) <= budget``)
    config maximizing modeled utility, ties to the lower draw — a
    deterministic argmax over the typed space, not a threshold ladder.
    ``run_fill`` executes queued ``PrimitiveJob``s when the chosen
    config schedules fill work, booking through the caller's meter.
    """

    def __init__(self, space: ConfigSpace | None = None,
                 cost: CostModel | None = None, *,
                 use_forecast: bool = True,
                 forecast_quantile: float = 0.25,
                 fill_max_intensity: float = 0.35,
                 fill_jobs: Iterable[PrimitiveJob] | None = None,
                 default_fill_size: int = 256):
        if not 0.0 <= forecast_quantile <= 1.0:
            raise ValueError(
                "ReconfigController: forecast_quantile must be in [0, 1], "
                f"got {forecast_quantile}")
        if fill_max_intensity < 0.0:
            raise ValueError(
                "ReconfigController: fill_max_intensity must be >= 0, "
                f"got {fill_max_intensity}")
        self.space = space or train_space()
        self.cost = cost or CostModel()
        self.use_forecast = use_forecast
        self.forecast_quantile = forecast_quantile
        # fill primitives are *deferrable* work: only worth buying when
        # the grid is clean (kg/kWh at the current interval below this
        # ceiling) — otherwise low-utility fill joules drag the
        # progress-per-kgCO2 figure of merit down instead of up
        self.fill_max_intensity = fill_max_intensity
        self.jobs: deque[PrimitiveJob] = deque(fill_jobs or ())
        self.default_fill_size = default_fill_size
        self.decisions: list[ReconfigDecision] = []
        self.fill_results: list[PrimitiveResult] = []

    def budget(self, supply_frac: float, forecast=None) -> float:
        """The fraction of full power this interval may draw: current
        supply, conservatively clipped by the forecast (same quantile
        semantics as CarbonAwareScheduler)."""
        b = float(supply_frac)
        if self.use_forecast and forecast is not None:
            b = min(b, resolve_forecast(forecast, self.forecast_quantile))
        return max(b, 0.0)

    def decide(self, supply_frac: float, forecast=None, *,
               intensity: float | None = None) -> ReconfigDecision:
        """Argmax utility over the feasible configs.  ``intensity``
        (kg/kWh at this interval, when the caller knows it) gates the
        deferrable fill rungs behind ``fill_max_intensity``."""
        b = self.budget(supply_frac, forecast)
        dirty = (intensity is not None
                 and float(intensity) > self.fill_max_intensity)
        best: HwConfig | None = None
        best_key: tuple | None = None
        for cfg in self.space:
            if dirty and cfg.fill is not None:
                continue
            p = self.cost.power_frac(cfg)
            if p > b + _EPS:
                continue
            key = (self.cost.utility(cfg), -p, cfg.name)
            if best_key is None or key > best_key:
                best, best_key = cfg, key
        if best is None:
            best = self.space.idle          # even idle_frac doesn't fit
        d = ReconfigDecision(
            config=best,
            power_frac=float(self.cost.power_frac(best)),
            utility=float(self.cost.utility(best)),
            budget_frac=b,
        )
        self.decisions.append(d)
        return d

    # -- fill dispatch -------------------------------------------------------
    def enqueue(self, job: PrimitiveJob) -> None:
        self.jobs.append(job)

    def run_fill(self, decision: ReconfigDecision, *, meter=None,
                 max_jobs: int = 1) -> list[PrimitiveResult]:
        """Execute up to ``max_jobs`` queued primitives in an interval
        whose chosen config schedules fill work.  With an empty queue a
        default job of the config's fill workload is synthesized (the
        substrate never idles when the budget can power a primitive).
        Each executed job books its measured wall time at the config's
        modeled draw through ``meter.fill`` and lands in
        ``EnergyReport.detail["reconfig"]["fill"]``."""
        if decision.config.fill is None:
            return []
        out = []
        for _ in range(max_jobs):
            if self.jobs:
                job = self.jobs.popleft()
            else:
                job = PrimitiveJob(decision.config.fill,
                                   size=self.default_fill_size,
                                   seed=len(self.fill_results))
            res = run_primitive(job)
            self.fill_results.append(res)
            out.append(res)
            if meter is not None:
                meter.fill(res.wall_s, workload=job.workload,
                           power_frac=decision.power_frac,
                           work_units=res.work_units)
        return out


# ---------------------------------------------------------------------------
# Trace replay: controller vs binary ladder on the same grid conditions
# ---------------------------------------------------------------------------


@dataclass
class ScheduleSummary:
    """One decider's account of a replayed supply trace."""
    progress: float                # useful-work units (full interval = 1.0)
    op_j: float
    co2_operational_kg: float
    embodied_j: float              # substrate amortization over the trace
    co2_embodied_kg: float
    intervals: int
    active_intervals: int          # model work executed
    fill_intervals: int            # fill primitive scheduled instead
    paused_intervals: int
    report: object                 # the meter's cumulative EnergyReport

    @property
    def co2_total_kg(self) -> float:
        return self.co2_operational_kg + self.co2_embodied_kg

    @property
    def progress_per_kgco2(self) -> float:
        """The paper's figure of merit: useful progress per total
        (operational + embodied) kgCO2."""
        return self.progress / max(self.co2_total_kg, _EPS)


def replay_supply(supply: np.ndarray, intensity: np.ndarray, *,
                  controller: ReconfigController | None = None,
                  scheduler=None,
                  interval_s: float = INTERVAL_S,
                  forecast=None,
                  execute_fill: bool = False,
                  meter=None) -> ScheduleSummary:
    """Replay a per-interval supply-fraction series through exactly one
    decider — a ``ReconfigController`` or a binary
    ``CarbonAwareScheduler`` — booking identical metering for both:
    operational energy at the decision's power scale, carbon at each
    interval's grid intensity, and the substrate's embodied share
    amortized over the whole trace wall clock (a paused interval still
    ages the silicon — that is the amortization argument).

    Binary progress accounting: RUN = 1, DERATE = its step scale (rate
    and draw scale together on the PAUSE/DERATE ladder), PAUSE = 0.
    Controller progress is the chosen config's modeled utility.
    ``execute_fill`` additionally runs one real ``PrimitiveJob`` per
    fill interval (capped) so the fill path is exercised end to end.
    """
    if (controller is None) == (scheduler is None):
        raise ValueError(
            "replay_supply: pass exactly one of controller= / scheduler=")
    from repro.core.ese import embodied
    from repro.core.ese.meter import MeterConfig, SustainabilityMeter

    supply = np.asarray(supply, float)
    intensity = np.asarray(intensity, float)
    if meter is None:
        meter = SustainabilityMeter(
            MeterConfig(carbon_intensity=intensity, steps_per_interval=1),
            name="reconfig" if controller is not None else "binary")
    progress = 0.0
    active = filled = paused = 0
    executed_fills = 0
    for i, s in enumerate(supply):
        f = None
        if forecast is not None:
            f = {float(q): float(v[i]) for q, v in forecast.items()}
        if controller is not None:
            d = controller.decide(float(s), f,
                                  intensity=float(intensity[i])
                                  if i < len(intensity) else None)
            cfg = d.config
            if cfg.is_idle:
                paused += 1
                meter.pause(interval_s, decision=d)
            elif cfg.step_scale == 0.0 and cfg.bucket_frac == 0.0:
                # fill-only config: no model work, primitive scheduled
                filled += 1
                meter.pause(interval_s, decision=d)
                if execute_fill and executed_fills < 3:
                    controller.run_fill(d, meter=meter)
                    executed_fills += 1
                else:
                    # modeled fill booking (the sweep replays thousands
                    # of intervals; executing every job would measure
                    # the host, not the schedule)
                    meter.fill(interval_s, workload=cfg.fill,
                               power_frac=d.power_frac, work_units=0.0,
                               executed=False)
            else:
                active += 1
                meter.step(interval_s, decision=d)
            progress += d.utility
        else:
            d = scheduler.decide(float(s), f)
            if d.action is Action.PAUSE:
                paused += 1
                meter.pause(interval_s)
            else:
                active += 1
                meter.step(interval_s, decision=d)
                progress += float(d.step_scale)
    # the substrate exists for the whole trace whether it ran or not
    chip = embodied.tpu_chip()
    emb_j = chip.embodied_j(len(supply) * interval_s * meter.cfg.chips)
    rep = meter.report()
    return ScheduleSummary(
        progress=progress,
        op_j=rep.operational_j,
        co2_operational_kg=rep.co2_operational_kg,
        embodied_j=emb_j,
        co2_embodied_kg=emb_j / 3.6e6 * meter.cfg.grid_kg_per_kwh,
        intervals=len(supply),
        active_intervals=active,
        fill_intervals=filled,
        paused_intervals=paused,
        report=rep,
    )
