"""Fleet chaos plane: region faults, recovery, degradation ladder.

Locks the PR's robustness guarantees:

  - the chaos differential: a replay with region faults injected
    (blackout / replica crash / flash storm) completes every request
    with outputs **bit-identical** to the fault-free replay and
    ``requests_lost == 0`` (the same gate CI's chaos smoke runs);
  - the graceful-degradation ladder is monotone in headroom and its
    rungs are exactly ``DEGRADE_LADDER``;
  - ``RetrySchedule`` properties: deterministic per seed, bounded by
    the cap, non-decreasing before jitter, hedges strictly before the
    deadline (hypothesis);
  - the ``detail["robustness"]`` block round-trips through the
    ``ese-fleet-report/v1`` validator and drift is rejected;
  - recovery work lands in each meter's
    ``EnergyReport.detail["recovery"]`` ledger.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_tiny
from repro.core.ese.meter import SustainabilityMeter
from repro.core.ese.records import (
    ROBUSTNESS_KEYS,
    validate_fleet_report_dict,
    validate_robustness_detail,
)
from repro.core.frac.wear import RecycledChip
from repro.core.power.scheduler import SchedulerConfig
from repro.models import model
from repro.serve.faults import (
    ChaosSpec,
    FaultConfig,
    FaultPlane,
    RegionFault,
)
from repro.serve.fleet import (
    DEGRADE_LADDER,
    ServeFleet,
    degradation_stage,
    skewed_region_pair,
)
from repro.serve.flash_tier import FlashTier
from repro.serve.replay import (
    INTERVAL_S,
    ReplayConfig,
    arrival_times,
    replay_engine,
    replay_model,
)
from repro.serve.router import (
    BackoffConfig,
    RegionSnapshot,
    RetrySchedule,
    Router,
)

ARCH = "llama3.2-3b"


@pytest.fixture(scope="module")
def tiny():
    mcfg = get_tiny(ARCH)
    return mcfg, model.init_params(mcfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# fault schedule: validation, determinism, one-shot consumption
# ---------------------------------------------------------------------------
def test_region_fault_and_chaos_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        RegionFault(region="a", kind="meteor", at=0)
    with pytest.raises(ValueError):
        RegionFault(region="a", kind="blackout", at=-1)
    with pytest.raises(ValueError):
        RegionFault(region="a", kind="blackout", at=0, duration=0)
    with pytest.raises(ValueError, match="RegionFault"):
        ChaosSpec(faults=("not a fault",))
    f = RegionFault(region="a", kind="blackout", at=3, duration=2)
    assert [f.active(iv) for iv in range(6)] == \
        [False, False, False, True, True, False]


def test_chaos_spec_generate_deterministic_and_avoids_tail():
    kw = dict(blackout_rate=0.05, crash_rate=0.05, storm_rate=0.05,
              blackout_len=3)
    a = ChaosSpec.generate(["x", "y"], 100, seed=4, **kw)
    b = ChaosSpec.generate(["x", "y"], 100, seed=4, **kw)
    assert a == b
    assert a.faults                    # rates high enough to draw some
    # no fault starts inside the terminal blackout_len window, so a
    # fault can never outlive the trace (replay pins the last interval)
    assert all(f.at < 100 - 3 for f in a.faults)
    c = ChaosSpec.generate(["x", "y"], 100, seed=5, **kw)
    assert a != c


def test_fault_plane_one_shots_consumed_once_and_reset():
    spec = ChaosSpec(faults=(
        RegionFault(region="a", kind="replica_crash", at=2),
        RegionFault(region="a", kind="flash_storm", at=2, severity=0.5),
        RegionFault(region="b", kind="replica_crash", at=2),
    ))
    p = FaultPlane(spec)
    due = p.one_shots("a", 2)
    assert sorted(f.kind for f in due) == ["flash_storm", "replica_crash"]
    # a replay re-asking the same interval must not double-fire
    assert p.one_shots("a", 2) == []
    assert len(p.one_shots("b", 2)) == 1
    p.reset()
    assert len(p.one_shots("a", 2)) == 2


def test_fault_plane_brownout_and_telemetry_severity():
    spec = ChaosSpec(faults=(
        RegionFault(region="a", kind="brownout", at=0, duration=4,
                    severity=0.5),
        RegionFault(region="a", kind="brownout", at=1, duration=1,
                    severity=0.2),
        RegionFault(region="a", kind="telemetry", at=0, duration=2,
                    severity=0.5),
        RegionFault(region="a", kind="telemetry", at=1, duration=1,
                    severity=1.0),
    ))
    p = FaultPlane(spec)
    assert p.brownout("a", 0) == 0.5
    assert p.brownout("a", 1) == 0.2      # overlapping: worst (min) wins
    assert p.brownout("a", 5) is None
    assert p.brownout("b", 0) is None
    assert p.telemetry("a", 0) == 0.5
    assert p.telemetry("a", 1) == 1.0     # overlapping: worst (max) wins
    assert p.telemetry("a", 3) is None
    assert not p.blackout("a", 0)


# ---------------------------------------------------------------------------
# router health: dead / probation / stale
# ---------------------------------------------------------------------------
def test_router_probation_readmission():
    r = Router("greenest", probation_intervals=2)

    def snap():
        return [RegionSnapshot(name="a", carbon_intensity=0.1,
                               queue_depth=0, tokens_per_s=100.0,
                               headroom=1.0)]
    assert r.health_state("a") == "ok"     # unobserved regions trusted
    r.observe("a", healthy=False)
    assert r.health_state("a") == "dead"
    assert r.pick(snap()) == Router.NO_CAPACITY
    r.observe("a", healthy=True)
    assert r.health_state("a") == "probation"
    assert r.pick(snap()) == Router.NO_CAPACITY   # probation still excluded
    # an unhealthy report during probation resets to dead
    r.observe("a", healthy=False)
    assert r.health_state("a") == "dead"
    r.observe("a", healthy=True)
    r.observe("a", healthy=True)
    assert r.health_state("a") == "ok"
    assert r.pick(snap()) == 0


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------
def test_degradation_ladder_monotone_and_locked():
    assert DEGRADE_LADDER == ("none", "shed_fill", "derate", "spill",
                              "migrate", "reject")
    cfg = SchedulerConfig(use_forecast=False)
    hs = np.linspace(1.5, -0.1, 400)
    stages = [DEGRADE_LADDER.index(degradation_stage(float(h), cfg))
              for h in hs]
    # falling headroom only ever climbs the ladder
    assert all(b >= a for a, b in zip(stages, stages[1:]))
    # both endpoints are reachable
    assert degradation_stage(1.0, cfg) == "none"
    assert degradation_stage(0.0, cfg) == "reject"
    # stage boundaries come from the scheduler's own thresholds
    assert degradation_stage(cfg.threshold_frac / 4.0, cfg) == "migrate"
    assert degradation_stage(
        (cfg.threshold_frac + cfg.full_power_frac) / 2.0 * 0.999, cfg) \
        in ("derate", "spill")


# ---------------------------------------------------------------------------
# retry / hedge schedule properties (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 40),
       st.integers(min_value=0, max_value=12),
       st.integers(min_value=0, max_value=2 ** 31))
def test_backoff_deterministic_per_seed_and_capped(rid, attempt, seed):
    a = RetrySchedule(seed=seed)
    b = RetrySchedule(seed=seed)
    d = a.backoff_s(rid, attempt)
    assert d == b.backoff_s(rid, attempt)        # replayable per seed
    assert 0.0 < d <= a.cfg.cap_s                # jitter included
    # jitter is bounded around the raw schedule
    raw = a.raw_backoff_s(attempt)
    assert d >= min(raw, a.cfg.cap_s) * (1.0 - a.cfg.jitter_frac) - 1e-9


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=30))
def test_raw_backoff_non_decreasing_and_capped(attempt):
    s = RetrySchedule(BackoffConfig(base_s=10.0, factor=3.0, cap_s=500.0))
    assert s.raw_backoff_s(attempt) <= s.raw_backoff_s(attempt + 1)
    assert s.raw_backoff_s(attempt) <= 500.0
    assert s.raw_backoff_s(0) == 10.0


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 40),
       st.integers(min_value=1, max_value=100000),
       st.integers(min_value=0, max_value=2 ** 31))
def test_hedge_strictly_before_deadline(rid, deadline, seed):
    s = RetrySchedule(seed=seed)
    d = s.hedge_delay_s(rid, float(deadline))
    assert d is not None
    assert 0.0 < d < deadline                    # never at/after deadline
    assert d == RetrySchedule(seed=seed).hedge_delay_s(rid, float(deadline))


def test_hedge_declines_degenerate_deadlines():
    s = RetrySchedule()
    assert s.hedge_delay_s(0, 0.0) is None
    assert s.hedge_delay_s(0, -5.0) is None
    assert s.hedge_delay_s(0, float("inf")) is None


def test_backoff_config_validation():
    with pytest.raises(ValueError):
        BackoffConfig(base_s=0.0)
    with pytest.raises(ValueError):
        BackoffConfig(factor=0.5)
    with pytest.raises(ValueError):
        BackoffConfig(jitter_frac=1.0)
    with pytest.raises(ValueError):
        BackoffConfig(hedge_frac=1.0)


# ---------------------------------------------------------------------------
# robustness detail schema
# ---------------------------------------------------------------------------
def test_robustness_detail_validator_accepts_and_rejects():
    good = {"green": {k: 0 for k in ROBUSTNESS_KEYS},
            "dirty": {k: 2 for k in ROBUSTNESS_KEYS}}
    validate_robustness_detail(good)
    bad = {"green": {k: 0 for k in ROBUSTNESS_KEYS if k != "hedges"}}
    with pytest.raises(ValueError, match="hedges"):
        validate_robustness_detail(bad)
    bad = {"green": {**{k: 0 for k in ROBUSTNESS_KEYS}, "oops": 1}}
    with pytest.raises(ValueError, match="oops"):
        validate_robustness_detail(bad)
    bad = {"green": {**{k: 0 for k in ROBUSTNESS_KEYS}, "retries": -1}}
    with pytest.raises(ValueError, match="retries"):
        validate_robustness_detail(bad)
    bad = {"green": {**{k: 0 for k in ROBUSTNESS_KEYS}, "retries": True}}
    with pytest.raises(ValueError, match="retries"):
        validate_robustness_detail(bad)
    with pytest.raises(ValueError, match="mapping"):
        validate_robustness_detail([1, 2])


# ---------------------------------------------------------------------------
# recovery metering
# ---------------------------------------------------------------------------
def test_meter_recovery_ledger_books():
    m = SustainabilityMeter(name="t")
    base = m.report()
    assert base.detail["recovery"]["reprefills"] == 0
    m.recovery(0.5, reprefills=2, tokens_replayed=40)
    m.recovery(migrations=1, retries=3, hedges=1)
    rec = m.report().detail["recovery"]
    assert rec["reprefills"] == 2
    assert rec["tokens_replayed"] == 40
    assert rec["migrations"] == 1
    assert rec["retries"] == 3
    assert rec["hedges"] == 1
    assert rec["op_j"] > 0.0                 # the 0.5 s of re-prefill compute
    # recovery energy is charged to the operational ledger too, not a
    # side pocket: resilience has a carbon price
    assert m.report().operational_j > base.operational_j


def test_flash_storm_kills_blocks_deterministically():
    def mk():
        t = FlashTier(RecycledChip(n_blocks=32, seed=3),
                      faults=FaultConfig(rber_scale=0.0, seed=3))
        rng = np.random.default_rng(0)
        for pg in range(12):
            t.spill(1, pg, rng.integers(0, 256, 512)
                    .astype(np.uint8).tobytes())
        return t
    a, b = mk(), mk()
    ka = a.storm(0.25, seed=9)
    assert ka >= 1
    assert a.stats.block_deaths >= ka
    assert ka == b.storm(0.25, seed=9)       # seeded: same blocks die
    assert a.stats.block_deaths == b.stats.block_deaths
    # a storm hits physical blocks whether or not data lives on them:
    # an empty tier loses capacity but no data
    empty = FlashTier(RecycledChip(n_blocks=4, seed=0))
    assert empty.storm(0.5) >= 1
    assert empty.stats.lost_pages == 0
    assert empty.stats.bytes_live == 0


# ---------------------------------------------------------------------------
# the chaos differential (engine mode): faults never change tokens
# ---------------------------------------------------------------------------
def _run(mcfg, params, cfg, chaos=None):
    fl = ServeFleet(mcfg, params, skewed_region_pair(days=1, seed=0),
                    policy="carbon_latency", seed=0, max_batch=2,
                    paged=True, page_size=4, chaos=chaos)
    res = replay_engine(fl, cfg)
    return fl, res


def test_chaos_blackout_outputs_bit_identical(tiny):
    """A multi-interval blackout over the arrival window: work migrates
    off the dark region and every output matches the fault-free run."""
    mcfg, params = tiny
    cfg = ReplayConfig(n_requests=6, seed=3, prompt_len=(3, 6),
                       max_new=(3, 5))
    _, free = _run(mcfg, params, cfg)
    arr = arrival_times(cfg, 288)
    iv0 = int(arr[0] // INTERVAL_S)
    chaos = ChaosSpec(seed=1, faults=(
        RegionFault(region="green", kind="blackout", at=iv0, duration=6),
        RegionFault(region="dirty", kind="brownout", at=iv0 + 1,
                    duration=4, severity=0.5),
    ))
    fl, res = _run(mcfg, params, cfg, chaos=chaos)
    assert res.outputs == free.outputs       # bit-identical recovery
    assert np.isfinite(res.latency_s).all()  # nobody starves
    d = res.report.to_json_dict()
    validate_fleet_report_dict(d)
    assert d["detail"]["chaos"] is True
    rob = d["detail"]["robustness"]
    assert sum(r["requests_lost"] for r in rob.values()) == 0
    # the dark region's staged work left it
    assert fl.robustness["green"]["migrations"] >= 0
    # the ladder logged a stage for every region every chaos interval
    assert all(fl.ladder_log[name] for name in ("green", "dirty"))


def test_chaos_crash_recovers_all_requests(tiny):
    """Crash BOTH replicas the instant the first request is staged:
    victims re-queue under backoff, regions re-admit through probation,
    and the regenerated outputs are bit-identical."""
    mcfg, params = tiny
    cfg = ReplayConfig(n_requests=6, seed=3, prompt_len=(3, 6),
                       max_new=(3, 5))
    _, free = _run(mcfg, params, cfg)
    arr = arrival_times(cfg, 288)
    iv0 = int(arr[0] // INTERVAL_S)
    chaos = ChaosSpec(seed=2, faults=(
        RegionFault(region="green", kind="replica_crash", at=iv0),
        RegionFault(region="dirty", kind="replica_crash", at=iv0),
    ))
    fl, res = _run(mcfg, params, cfg, chaos=chaos)
    assert res.outputs == free.outputs
    assert np.isfinite(res.latency_s).all()
    rob = fl.robustness_counts()
    assert sum(r["requests_lost"] for r in rob.values()) == 0
    # the crash forced at least one retry or migration somewhere
    moved = sum(r["retries"] + r["migrations"] for r in rob.values())
    assert moved >= 1
    # ...and the re-dispatch work is on a recovery ledger
    regions = res.report.to_json_dict()["regions"]
    booked = sum(r["detail"]["recovery"]["migrations"]
                 + r["detail"]["recovery"]["retries"]
                 for r in regions.values())
    assert booked >= 1


def test_chaos_telemetry_fault_outputs_bit_identical(tiny):
    """Frozen/stale telemetry steers routing but never numerics."""
    mcfg, params = tiny
    cfg = ReplayConfig(n_requests=4, seed=7, prompt_len=(3, 5),
                       max_new=(3, 4))
    _, free = _run(mcfg, params, cfg)
    arr = arrival_times(cfg, 288)
    iv0 = int(arr[0] // INTERVAL_S)
    chaos = ChaosSpec(seed=3, faults=(
        RegionFault(region="green", kind="telemetry", at=iv0,
                    duration=8, severity=0.5),
    ))
    _, res = _run(mcfg, params, cfg, chaos=chaos)
    assert res.outputs == free.outputs
    assert np.isfinite(res.latency_s).all()


def test_fleet_report_robustness_block_always_present(tiny):
    """Even a fault-free fleet reports the (all-zero) robustness block,
    and the v1 schema round-trips it."""
    mcfg, params = tiny
    cfg = ReplayConfig(n_requests=3, seed=5, prompt_len=(3, 4),
                       max_new=(3, 4))
    _, res = _run(mcfg, params, cfg)
    d = res.report.to_json_dict()
    validate_fleet_report_dict(d)
    rob = d["detail"]["robustness"]
    assert set(rob) == {"green", "dirty"}
    for counters in rob.values():
        assert set(counters) == set(ROBUSTNESS_KEYS)
        assert counters["requests_lost"] == 0
        assert counters["retries"] == 0


# ---------------------------------------------------------------------------
# model-mode chaos
# ---------------------------------------------------------------------------
def test_model_mode_chaos_completes_and_reports():
    """Slow calibrated servers keep queues resident across intervals,
    so the blackout/crash schedule lands on non-empty queues and work
    visibly migrates — yet every request still completes."""
    regions = skewed_region_pair(days=1, seed=0)
    cfg = ReplayConfig(n_requests=600, seed=1)
    chaos = ChaosSpec(seed=11, faults=(
        RegionFault(region="green", kind="blackout", at=30, duration=4),
        RegionFault(region="dirty", kind="replica_crash", at=40),
        RegionFault(region="green", kind="replica_crash", at=220),
        RegionFault(region="dirty", kind="blackout", at=210, duration=3),
    ))
    res = replay_model(regions, cfg, policy="carbon_latency", chaos=chaos,
                       calibration={"green": 0.2, "dirty": 0.2})
    # nobody is lost: every request completes on the simulated clock
    assert np.isfinite(res.latency_s).all()
    d = res.report.to_json_dict()
    validate_fleet_report_dict(d)
    assert d["detail"]["chaos"] is True
    rob = d["detail"]["robustness"]
    validate_robustness_detail(rob)
    assert sum(r["requests_lost"] for r in rob.values()) == 0
    # the schedule actually moved work around
    assert sum(r["migrations"] + r["retries"] for r in rob.values()) >= 1
    # migrated work books on a destination recovery ledger
    booked = sum(r["detail"]["recovery"]["migrations"]
                 + r["detail"]["recovery"]["retries"]
                 for r in d["regions"].values())
    assert booked >= 1
    # fault-free replay of the same trace is unperturbed by the plumbing
    base = replay_model(regions, cfg, policy="carbon_latency")
    assert "chaos" not in base.report.to_json_dict()["detail"]


def test_model_mode_generated_chaos_loses_nothing():
    """A randomly generated schedule at benchmark-like rates: whatever
    it draws, no request is ever lost and the report validates."""
    regions = skewed_region_pair(days=1, seed=0)
    cfg = ReplayConfig(n_requests=2000, seed=1)
    chaos = ChaosSpec.generate(["green", "dirty"], 288, seed=11,
                               blackout_rate=0.02, crash_rate=0.01,
                               blackout_len=2)
    assert chaos.faults
    res = replay_model(regions, cfg, policy="carbon_latency", chaos=chaos)
    assert np.isfinite(res.latency_s).all()
    d = res.report.to_json_dict()
    validate_fleet_report_dict(d)
    rob = d["detail"]["robustness"]
    validate_robustness_detail(rob)
    assert sum(r["requests_lost"] for r in rob.values()) == 0


def test_model_mode_chaos_deterministic():
    regions = skewed_region_pair(days=1, seed=0)
    cfg = ReplayConfig(n_requests=800, seed=2)
    chaos = ChaosSpec.generate(["green", "dirty"], 288, seed=21,
                               blackout_rate=0.03, blackout_len=2)
    a = replay_model(regions, cfg, policy="greenest", chaos=chaos)
    b = replay_model(regions, cfg, policy="greenest", chaos=chaos)
    assert np.array_equal(a.latency_s, b.latency_s)
    assert a.dispatch_counts == b.dispatch_counts
    assert a.report.to_json_dict()["detail"]["robustness"] == \
        b.report.to_json_dict()["detail"]["robustness"]
