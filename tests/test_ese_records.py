"""Typed ESE API: record validation, JSON schema round-trip, the legacy
dict adapter, and the online SustainabilityMeter."""
import json

import jax
import numpy as np
import pytest

from repro.core.ese import estimator
from repro.core.ese.meter import MeterConfig, SustainabilityMeter
from repro.core.ese.records import (
    REPORT_SCHEMA,
    EnergyReport,
    RooflineRecord,
    TaskSpec,
    roofline_records,
    validate_report_dict,
)
from repro.core.power.scheduler import CarbonAwareScheduler
from repro.core.power.traces import make_trace

RL = {
    "t_compute_s": 0.4, "t_memory_s": 0.9, "t_collective_s": 0.2,
    "flops_per_device": 8e13, "hbm_bytes_per_device": 7e11,
    "collective_bytes_per_device": 1e10,
    "step_time_bound_s": 0.9, "chips": 256,
}


# -- RooflineRecord ----------------------------------------------------------

def test_roofline_record_round_trip():
    rec = RooflineRecord.from_dict(RL)
    assert rec.chips == 256 and rec.step_time_bound_s == 0.9
    d = rec.to_dict()
    assert RooflineRecord.from_dict(d) == rec
    # every input key survives the round trip
    for k, v in RL.items():
        assert d[k] == v


def test_roofline_record_matches_launch_roofline():
    from repro.launch.roofline import Roofline

    rl = Roofline(flops=1e12, hbm_bytes=1e10, collective_bytes=1e9,
                  model_flops=2e14, chips=64, device_kind="TPU v5 lite")
    rec = RooflineRecord.from_dict(rl.as_dict())
    # the typed record reproduces the dry-run on-disk schema exactly
    assert rec.to_dict() == rl.as_dict()


@pytest.mark.parametrize("missing", ["t_compute_s", "chips",
                                     "step_time_bound_s"])
def test_roofline_record_names_missing_key(missing):
    bad = {k: v for k, v in RL.items() if k != missing}
    with pytest.raises(ValueError, match=missing):
        RooflineRecord.from_dict(bad)


def test_roofline_record_names_ill_typed_key():
    bad = dict(RL, chips="256")
    with pytest.raises(ValueError, match="chips"):
        RooflineRecord.from_dict(bad)
    bad = dict(RL, t_memory_s=None)
    with pytest.raises(ValueError, match="t_memory_s"):
        RooflineRecord.from_dict(bad)
    bad = dict(RL, t_memory_s=True)   # bools are not energies
    with pytest.raises(ValueError, match="t_memory_s"):
        RooflineRecord.from_dict(bad)
    with pytest.raises(ValueError, match="chips"):
        RooflineRecord.from_dict(dict(RL, chips=0))
    with pytest.raises(ValueError, match="t_compute_s"):
        RooflineRecord.from_dict(dict(RL, t_compute_s=-1.0))


def test_roofline_record_from_cell():
    assert RooflineRecord.from_cell({"roofline": RL}) \
        == RooflineRecord.from_dict(RL)
    assert RooflineRecord.from_cell(RL) == RooflineRecord.from_dict(RL)
    with pytest.raises(ValueError, match="roofline"):
        RooflineRecord.from_cell({"arch": "llama", "skipped": "x"})
    with pytest.raises(ValueError, match="mapping"):
        RooflineRecord.from_cell([RL])


def test_roofline_records_filters_unusable_cells():
    cells = [{"roofline": RL, "tag": "baseline"},
             {"skipped": "long_500k"},
             {"error": "OOM"},
             RooflineRecord.from_dict(RL)]
    recs = roofline_records(cells)
    assert len(recs) == 2
    assert all(isinstance(r, RooflineRecord) for r in recs)


def test_roofline_record_is_a_pytree():
    rec = RooflineRecord.from_dict(RL)
    leaves = jax.tree.leaves(rec)
    assert len(leaves) == 10          # numeric terms; chips/dominant static
    doubled = jax.tree.map(lambda x: x * 2, rec)
    assert isinstance(doubled, RooflineRecord)
    assert doubled.t_compute_s == pytest.approx(2 * rec.t_compute_s)
    assert doubled.chips == rec.chips


# -- TaskSpec ----------------------------------------------------------------

def test_task_spec_validation():
    spec = TaskSpec.from_dict({"n_steps": 100, "net_demand_quantile": 0.3,
                               "recycled_optin": True})
    assert spec.n_steps == 100 and spec.recycled_optin
    assert TaskSpec.from_dict(spec.to_dict()) == spec
    with pytest.raises(ValueError, match="net_demand_quantile"):
        TaskSpec(net_demand_quantile=1.5)
    with pytest.raises(ValueError, match="n_steps"):
        TaskSpec(n_steps=-1)
    with pytest.raises(ValueError, match="recycled_optin"):
        TaskSpec.from_dict({"recycled_optin": "yes"})


# -- EnergyReport JSON schema ------------------------------------------------

def _report() -> EnergyReport:
    rec = RooflineRecord.from_dict(RL)
    return estimator.estimate(rec, TaskSpec(n_steps=100,
                                            net_demand_quantile=0.2))


def test_energy_report_json_round_trip():
    rep = _report()
    blob = json.dumps(rep.to_json_dict())      # survives real JSON
    back = EnergyReport.from_json_dict(json.loads(blob))
    assert back == rep
    assert back.detail["bill"] == rep.detail["bill"]
    assert back.total_j == pytest.approx(rep.operational_j + rep.embodied_j)


def test_energy_report_schema_drift_detected():
    good = _report().to_json_dict()
    assert good["schema"] == REPORT_SCHEMA
    validate_report_dict(good)

    bad = dict(good, schema="ese-energy-report/v0")
    with pytest.raises(ValueError, match="schema"):
        validate_report_dict(bad)
    bad = {k: v for k, v in good.items() if k != "operational_j"}
    with pytest.raises(ValueError, match="operational_j"):
        validate_report_dict(bad)
    bad = dict(good, co2_kg={"total": 1.0})
    with pytest.raises(ValueError, match="operational"):
        validate_report_dict(bad)
    bad = dict(good, bill={"policy": "carbon_aware"})
    with pytest.raises(ValueError, match="usd"):
        validate_report_dict(bad)


# -- legacy dict adapter -----------------------------------------------------

def test_estimate_task_legacy_dict_adapter():
    with pytest.warns(DeprecationWarning, match="RooflineRecord"):
        legacy = estimator.estimate_task({"roofline": RL}, n_steps=100,
                                         net_demand_quantile=0.2)
    typed = _report()
    assert legacy.bill_usd == pytest.approx(typed.bill_usd)
    assert legacy.operational_j == pytest.approx(typed.operational_j)
    # typed records go straight through, no warning
    rep = estimator.estimate_task(RooflineRecord.from_dict(RL), n_steps=100,
                                  net_demand_quantile=0.2)
    assert rep == typed


def test_estimate_task_legacy_names_bad_key():
    """Malformed legacy records raise ValueError naming the key, not a
    KeyError from deep inside energy.py."""
    bad = {k: v for k, v in RL.items() if k != "t_collective_s"}
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="t_collective_s"):
            estimator.estimate_task({"roofline": bad}, n_steps=10)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="roofline"):
            estimator.estimate_task({"arch": "llama"}, n_steps=10)


# -- SustainabilityMeter -----------------------------------------------------

def test_meter_books_steps_and_attributes_scheduler():
    sch = CarbonAwareScheduler()
    m = SustainabilityMeter(MeterConfig(chips=4), name="train")
    full = m.step(0.5, decision=sch.decide(1.0), tokens=128)
    assert full.operational_j == pytest.approx(m.facility_w * 0.5)
    assert full.embodied_j > 0 and full.co2_kg > 0

    derated = m.step(0.5, decision=sch.decide(0.5), tokens=128)
    scale = sch.decide(0.5).step_scale
    assert derated.operational_j == pytest.approx(
        m.facility_w * scale * 0.5)
    m.pause()

    rep = m.report()
    sched = rep.detail["scheduler"]
    assert sched["paused_steps"] == 1 and sched["derated_steps"] == 1
    assert sched["avoided_derate_j"] == pytest.approx(
        m.facility_w * (1 - scale) * 0.5)
    # pause avoided a whole interval at the EWMA step time
    assert sched["avoided_pause_j"] > 0
    assert sched["avoided_j"] == pytest.approx(
        sched["avoided_pause_j"] + sched["avoided_derate_j"])
    assert rep.operational_j == pytest.approx(
        full.operational_j + derated.operational_j)
    assert rep.task.n_steps == 3              # 2 executed + 1 paused interval
    validate_report_dict(rep.to_json_dict())


def test_meter_carbon_intensity_follows_grid_trace():
    trace = make_trace(days=1, seed=0)
    ci = trace.carbon_intensity_kg_per_kwh
    assert ci.min() >= 0.0 and ci.max() <= 0.40 + 1e-9
    # solar noon is cleaner than midnight on this synthetic CAISO day
    assert ci[144] < ci[0]                    # 12:00 vs 00:00 (5-min steps)

    m = SustainabilityMeter.from_trace(trace, steps_per_interval=1)
    assert m.carbon_intensity() == pytest.approx(float(ci[0]))
    r_night = m.step(1.0)
    for _ in range(143):
        m.step(1.0)
    r_noon = m.step(1.0)                      # interval 144
    assert r_noon.co2_operational_kg < r_night.co2_operational_kg


def test_meter_interval_cursor_advances_and_seeks():
    trace = make_trace(days=1, seed=0)
    ci = trace.carbon_intensity_kg_per_kwh
    # requests advance the grid cursor just like steps, so a long-lived
    # serving meter doesn't stay pinned at interval 0
    m = SustainabilityMeter.from_trace(trace, steps_per_interval=1)
    m.request(8, 0.1)
    m.step(0.1)
    m.pause(0.1)
    assert m.carbon_intensity() == pytest.approx(float(ci[3]))
    # a resumed trainer seeks the meter to its absolute step so both
    # read the same grid intervals
    m2 = SustainabilityMeter.from_trace(trace, steps_per_interval=1)
    m2.seek(144)
    assert m2.carbon_intensity() == pytest.approx(float(ci[144]))


def test_meter_pause_before_first_step_books_avoided_energy():
    """A run that starts in a low-supply window pauses before any step
    time has been measured — the hint/roofline fallback keeps the
    avoided-energy attribution from silently reading zero."""
    m = SustainabilityMeter(MeterConfig(step_s_hint=0.25))
    m.pause()
    assert m.totals.avoided_pause_j == pytest.approx(m.facility_w * 0.25)

    # no hint, no roofline (the Trainer default): leading pauses are
    # held back and booked retroactively at the first measured step time
    m0 = SustainabilityMeter(MeterConfig())
    m0.pause()
    m0.pause()
    assert m0.totals.paused_steps == 2
    assert m0.totals.avoided_pause_j == 0.0
    m0.step(0.2)
    assert m0.totals.avoided_pause_j == pytest.approx(
        2 * m0.facility_w * 0.2)

    rec = RooflineRecord.from_dict(RL)
    m2 = SustainabilityMeter(MeterConfig(chips=rec.chips, roofline=rec))
    m2.pause()
    assert m2.totals.avoided_pause_j == pytest.approx(
        m2.facility_w * rec.step_time_bound_s)
    # measured steps take over from the hint
    m2.step(0.1)
    m2.pause()
    assert m2.totals.avoided_pause_j == pytest.approx(
        m2.facility_w * (rec.step_time_bound_s + 0.1))


def test_meter_request_charges_flash_occupancy():
    m = SustainabilityMeter(MeterConfig(), name="serve")
    rep = m.request(64, 2.0, rid=7, kv_frac_bytes=10_000_000,
                    kv_occupancy_s=2.0)
    assert rep.task.name == "serve/request7"
    assert rep.detail["tokens"] == 64
    assert rep.detail["j_per_token"] == pytest.approx(rep.total_j / 64)
    # the FRAC KV bytes were charged through the recycled flash tier
    assert "nand-tb" in m.footprint.by_unit
    assert m.footprint.by_unit["nand-tb"]["embodied_j"] > 0
    # recycled discount applied: TBE·occupancy/lifetime · discount
    from repro import hw
    want = (1.5e9 * hw.RECYCLED_TBE_DISCOUNT
            * (2.0 * 10_000_000 / 1e12) / (4 * 365 * 24 * 3600.0))
    assert m.footprint.by_unit["nand-tb"]["embodied_j"] == pytest.approx(want)


def test_meter_config_validated_at_construction():
    """Bad meter configs fail when the meter is built, not on the first
    reading mid-run."""
    with pytest.raises(ValueError, match="net_demand_quantile"):
        SustainabilityMeter(MeterConfig(net_demand_quantile=1.2))
    with pytest.raises(ValueError, match="chips"):
        SustainabilityMeter(MeterConfig(chips=0))


def test_estimate_task_legacy_clips_quantile():
    """The compatibility adapter keeps the old billing tolerance for
    out-of-range quantiles (TaskSpec itself stays strict)."""
    with pytest.warns(DeprecationWarning):
        hi = estimator.estimate_task({"roofline": RL}, n_steps=10,
                                     net_demand_quantile=1.7)
    with pytest.warns(DeprecationWarning):
        capped = estimator.estimate_task({"roofline": RL}, n_steps=10,
                                         net_demand_quantile=1.0)
    assert hi.bill_usd == pytest.approx(capped.bill_usd)


def test_meter_white_box_power_from_roofline():
    rec = RooflineRecord.from_dict(RL)
    from repro.core.ese import energy
    m = SustainabilityMeter(MeterConfig(chips=rec.chips, roofline=rec))
    se = energy.operational_step_energy(rec)
    assert m.facility_w == pytest.approx(se.breakdown["facility_w"])
    r = m.step(rec.step_time_bound_s)
    assert r.operational_j == pytest.approx(se.step_j, rel=1e-6)
