"""ESE: predictor quantiles, energy models, embodied formula, billing."""
import numpy as np
import pytest

from repro.core.ese import billing, embodied, energy, predictor
from repro.core.ese.records import RooflineRecord
from repro.core.power import traces


@pytest.fixture(scope="module")
def trained():
    tr = traces.make_trace(days=6, seed=1)
    cfg = predictor.PredictorConfig(steps=350, hidden=32, context=12)
    return predictor.train(tr, cfg)


def test_predictor_learns_and_covers(trained):
    params, norms, metrics = trained
    # pinball below the trivial constant-median predictor (~0.4 on
    # standardized targets)
    assert metrics["pinball_test"] < 0.25
    # the [P2.5, P97.5] band covers a solid majority of the truth (the
    # smoke-scale prototype under-covers vs nominal 95% — the paper's
    # own prototype reports similar fluctuation, Fig 7)
    assert metrics["coverage95_net"] > 0.4
    assert metrics["coverage95_renew"] > 0.4


def test_quantiles_ordered(trained):
    params, norms, _ = trained
    tr = traces.make_trace(days=2, seed=9)
    cfg = predictor.PredictorConfig(steps=0, hidden=32, context=12)
    split, _ = predictor.make_dataset(tr, cfg)
    import jax.numpy as jnp

    x = jnp.asarray(split["test"][0][:32])
    out = np.asarray(predictor.forward(params, x))
    B = out.shape[0]
    qht = out.reshape(B, len(predictor.QUANTILES), -1)
    # median larger than P2.5, smaller than P97.5 for most samples
    frac = ((qht[:, 0] <= qht[:, 3]) & (qht[:, 3] <= qht[:, -1])).mean()
    assert frac > 0.85


def _roofline(**kw) -> RooflineRecord:
    base = dict(step_time_bound_s=1.0, t_compute_s=1.0, t_memory_s=0.5,
                t_collective_s=0.1, flops_per_device=1e14,
                hbm_bytes_per_device=5e11, collective_bytes_per_device=2e10,
                chips=256)
    base.update(kw)
    return RooflineRecord(**base)


def test_operational_energy_model():
    se = energy.operational_step_energy(_roofline())
    from repro import hw

    assert hw.V5E.idle_w < se.chip_w <= hw.V5E.tdp_w
    # facility overheads: PUE and delivery loss are applied
    base = (se.chip_w + hw.HOST_OVERHEAD_W) * 256
    assert se.step_j == pytest.approx(base * 1.06 * hw.PUE, rel=1e-6)


def test_operational_energy_rejects_raw_dicts():
    rl = {"step_time_bound_s": 1.0, "t_compute_s": 1.0,
          "t_memory_s": 0.5, "t_collective_s": 0.1}
    with pytest.raises(TypeError, match="RooflineRecord"):
        energy.operational_step_energy(rl, chips=256)


def test_embodied_formula_verbatim():
    u = embodied.HardwareUnit("x", tbe_j=1000.0, lifetime_s=100.0)
    # E = TBE * latency / lifetime
    assert u.embodied_j(10.0) == pytest.approx(100.0)
    r = embodied.HardwareUnit("x", 1000.0, 100.0, recycled=True)
    from repro import hw

    assert r.embodied_j(10.0) == pytest.approx(100.0 * hw.RECYCLED_TBE_DISCOUNT)


def test_footprint_accumulates():
    fp = embodied.TaskFootprint()
    fp.charge(embodied.tpu_chip(), 3600.0, operational_j=1e6)
    fp.charge(embodied.flash_tb(), 3600.0)
    assert fp.total_j > 1e6 and "tpu-v5e" in fp.by_unit
    assert fp.co2_kg() > 0


def test_billing_edges_golden():
    """Lock the carbon-aware tariff at the quantile extremes and both
    recycled opt-in settings (1 kWh operational + 0.1 kWh embodied)."""
    op, emb = 3.6e6, 3.6e5
    cases = {
        # (net_demand_quantile, recycled_optin) -> golden USD
        (0.0, False): 0.206,      # no surge: 0.18 + 0.1·0.26
        (0.0, True): 0.1969,      # green discount on the embodied rate
        (1.0, False): 0.476,      # full 2.5x surge on operational
        (1.0, True): 0.4669,
    }
    for (q, rec), usd in cases.items():
        bill = billing.carbon_aware(op, emb, net_demand_quantile=q,
                                    recycled_optin=rec)
        assert bill.usd == pytest.approx(usd, rel=1e-9), (q, rec)
        assert bill.breakdown["surge"] == pytest.approx(
            1.0 if q == 0.0 else 2.5)
    # derate opt-in stacks multiplicatively on the discounted bill
    b = billing.carbon_aware(op, emb, net_demand_quantile=1.0,
                             recycled_optin=True, derate_optin=True)
    assert b.usd == pytest.approx(0.4669 * 0.8, rel=1e-9)
    # out-of-range quantiles clip to the edges
    lo = billing.carbon_aware(op, emb, net_demand_quantile=-3.0)
    hi = billing.carbon_aware(op, emb, net_demand_quantile=7.0)
    assert lo.usd == pytest.approx(0.206, rel=1e-9)
    assert hi.usd == pytest.approx(0.476, rel=1e-9)


def test_footprint_co2_split_golden():
    """TaskFootprint CO2 operational/embodied split — golden numbers for
    1e6 J operational + one chip-hour embodied."""
    fp = embodied.TaskFootprint()
    fp.charge(embodied.tpu_chip(), 3600.0, operational_j=1e6)
    assert fp.embodied_j == pytest.approx(98173.51598173517, rel=1e-12)
    split = fp.co2_split_kg()
    assert split["operational"] == pytest.approx(0.06666666666666667)
    assert split["embodied"] == pytest.approx(0.0065449010654490105)
    assert fp.co2_kg() == pytest.approx(split["operational"]
                                        + split["embodied"])
    # embodied carbon may carry its own (manufacture-time) intensity
    split2 = fp.co2_split_kg(embodied_kg_per_kwh=0.48)
    assert split2["embodied"] == pytest.approx(2 * split["embodied"])
    assert split2["operational"] == pytest.approx(split["operational"])


def test_billing_incentives():
    op, emb = 3.6e6, 3.6e5       # 1 kWh op, 0.1 kWh embodied
    flat = billing.flat(op, emb)
    surge = billing.carbon_aware(op, emb, net_demand_quantile=1.0)
    green = billing.carbon_aware(op, emb, net_demand_quantile=1.0,
                                 recycled_optin=True, derate_optin=True)
    offpeak = billing.carbon_aware(op, emb, net_demand_quantile=0.0)
    assert surge.usd > flat.usd            # scarce renewables cost more
    assert green.usd < surge.usd           # green opt-ins are rewarded
    assert offpeak.usd <= flat.usd + 1e-9  # abundant renewables are cheap


def test_serve_meter_books_only_decoded_tokens():
    """Early exit must book exactly the tokens actually decoded — a
    bucket killed by EOS before max_new charges J for its real tokens,
    not the horizon (trainer-style accounting identities)."""
    import jax
    import pytest as _pytest

    from repro.configs import get_tiny
    from repro.core.ese.meter import MeterConfig, SustainabilityMeter
    from repro.models import model
    from repro.serve.engine import ServeEngine

    mcfg = get_tiny("llama3.2-3b")
    params = model.init_params(mcfg, jax.random.PRNGKey(0))
    probe = ServeEngine(mcfg, params, max_batch=1)
    pr = probe.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    ref = probe.run()[pr]
    eos = ref[-1]
    want = ref[: ref.index(eos) + 1]
    meter = SustainabilityMeter(MeterConfig(flat_w=100.0), name="serve")
    eng = ServeEngine(mcfg, params, max_batch=2, eos_id=eos, meter=meter)
    r1 = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    r2 = eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=8)
    res = eng.run()
    assert res[r1] == want                    # early exit happened
    # golden identities: booked tokens == decoded tokens, per request
    # and in total; J split across the bucket proportional to tokens
    for rid in (r1, r2):
        assert eng.reports[rid].detail["tokens"] == len(res[rid])
    assert meter.totals.tokens == len(res[r1]) + len(res[r2])
    assert meter.totals.requests == 2
    share = {rid: eng.reports[rid].operational_j / max(len(res[rid]), 1)
             for rid in (r1, r2)}
    assert share[r1] == _pytest.approx(share[r2], rel=1e-6)
    total = eng.energy_report()
    assert total.operational_j == _pytest.approx(
        sum(r.operational_j for r in eng.reports.values()))


def test_paged_serve_books_allocated_pages_only():
    """Paged FRAC KV golden: ``kv_bytes_frac`` equals the codec's
    ``compressed_nbytes`` summed over *allocated pages only* (each page
    an independent packed stream), strictly below what the bucket-max
    contiguous layout books for the same skewed bucket — the honest
    resident-bytes number behind the flash-tier embodied charge."""
    import jax

    from repro.configs import get_tiny
    from repro.kernels.frac_pack import ops as fops
    from repro.models import model
    from repro.models.common import is_leaf_spec
    from repro.serve.engine import ServeEngine
    from repro.serve.paging import pages_for

    mcfg = get_tiny("llama3.2-3b")
    params = model.init_params(mcfg, jax.random.PRNGKey(0))
    ps, kbits = 16, 8
    plens, max_new = [4, 24], [4, 8]
    eng = ServeEngine(mcfg, params, max_batch=2, paged=True, page_size=ps,
                      kv_frac_kbits=kbits)
    rids = [eng.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=m)
            for n, m in zip(plens, max_new)]
    res = eng.run()
    # per-page stream bytes over every layer's k/v pool leaf
    specs = model.paged_pool_specs(mcfg, 2, ps)
    page_frac = page_full = 0
    for s in jax.tree.leaves(specs, is_leaf=is_leaf_spec):
        elems = int(np.prod(s.shape[2:]))
        page_frac += s.shape[0] * fops.compressed_nbytes_pages(1, elems, kbits)
        page_full += s.shape[0] * elems * 2                  # bf16
    # pages a request actually allocated: prompt pages grown by the
    # decode writes it made (its last KV row is len + emitted - 2)
    pages = [max(pages_for(n, ps), pages_for(n + len(res[r]) - 1, ps))
             for n, r in zip(plens, rids)]
    assert eng.stats.kv_bytes_frac == sum(pages) * page_frac
    assert eng.stats.kv_bytes_full == sum(pages) * page_full
    for r, npages in zip(rids, pages):
        assert eng.reports[r].detail["kv_frac_bytes"] == npages * page_frac
    assert "nand-tb" in eng.meter.footprint.by_unit
    # strictly below the contiguous bucket-max accounting for the same
    # skewed bucket (what the PR 4 engine would book)
    S, horizon = max(plens), max(max_new)
    contig_specs = model.cache_specs(mcfg, len(plens), S + horizon)
    contig_frac = sum(
        fops.compressed_nbytes(int(np.prod(s.shape)), kbits)
        for s in jax.tree.leaves(contig_specs, is_leaf=is_leaf_spec))
    assert eng.stats.kv_bytes_frac < contig_frac
    assert eng.stats.kv_bytes_peak < len(plens) * (S + horizon) * (
        page_full // ps)


def test_latency_head_on_synthetic_records():
    rng = np.random.default_rng(0)
    recs = []
    for i in range(40):
        t = float(rng.uniform(0.05, 5.0))
        recs.append(_roofline(
            t_compute_s=t, t_memory_s=t * rng.uniform(0.3, 2.0),
            t_collective_s=t * rng.uniform(0.05, 0.8),
            flops_per_device=t * 1e14, hbm_bytes_per_device=t * 5e11,
            collective_bytes_per_device=t * 2e10,
            step_time_bound_s=t,
        ))
    params, norm, mape = energy.train_latency_head(recs, steps=500)
    assert mape < 0.25, f"learned latency head MAPE {mape}"
    # un-converted dry-run cells are rejected with a pointer to the fix
    with pytest.raises(TypeError, match="roofline_records"):
        energy.train_latency_head([{"roofline": recs[0].to_dict()}])
