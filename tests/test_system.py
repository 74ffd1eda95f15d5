"""End-to-end behaviour tests for the paper's system.

train → checkpoint → resume → reshard (elastic) → serve → ESE bill, on a
tiny config — the full Verdant lifecycle on CPU.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_tiny
from repro.core.ese import estimator
from repro.data.pipeline import DataStream, make_batch
from repro.serve.engine import ServeEngine
from repro.train.loop import Trainer, TrainerConfig

ARCH = "llama3.2-3b"


def test_full_lifecycle(tmp_path):
    mcfg = get_tiny(ARCH)
    tcfg = TrainerConfig(total_steps=10, global_batch=2, seq_len=16,
                         ckpt_dir=str(tmp_path), ckpt_every=5,
                         snapshot_mode="frac8")
    out = Trainer(mcfg, tcfg).run()
    assert out["final_step"] == 10 and np.isfinite(out["final_loss"])
    # the trainer metered the run: per-step energy + cumulative report
    from repro.core.ese.records import EnergyReport, validate_report_dict
    assert isinstance(out["energy_report"], EnergyReport)
    assert out["energy_report"].operational_j > 0
    assert all(m["energy_j"] > 0 for m in out["metrics"])
    validate_report_dict(out["energy_report"].to_json_dict())

    # serve from the trained params
    eng = ServeEngine(mcfg, out["params"], max_batch=2)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=4)
    res = eng.run()
    assert all(len(v) == 4 for v in res.values())
    assert eng.stats.prefills == 1     # same-length bucket batched
    # per-request EnergyReports: J/token booked for both requests
    assert set(eng.reports) == set(res)
    for rep in eng.reports.values():
        assert rep.detail["tokens"] == 4
        assert rep.detail["j_per_token"] > 0
    assert eng.energy_report().operational_j == pytest.approx(
        sum(r.operational_j for r in eng.reports.values()))


def test_serve_frac_kv_cache():
    """FRAC KV-cache dial: decode still produces tokens and the stats
    book the modeled k/32 capacity win — now over the whole decode
    horizon, since decode-written slots are quantized in the loop."""
    mcfg = get_tiny(ARCH)
    from repro.models import model as m
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    eng = ServeEngine(mcfg, params, max_batch=2, kv_frac_kbits=8)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=4)
    res = eng.run()
    assert all(len(v) == 4 for v in res.values())
    assert eng.stats.kv_bytes_full > 0
    # 8-bit codes on bf16/fp32 KV + scales: at least ~1.9x smaller
    assert eng.stats.kv_bytes_frac < eng.stats.kv_bytes_full / 1.9
    # byte accounting is exactly the codec's single source of truth over
    # every float leaf of the grown (prompt + decode horizon) cache
    from repro.kernels.frac_pack import ops as fops
    from repro.models.common import is_leaf_spec
    specs = m.cache_specs(mcfg, 2, 8 + 4)
    leaves = jax.tree.leaves(specs, is_leaf=is_leaf_spec)
    expect_frac = sum(
        fops.compressed_nbytes(int(np.prod(s.shape)), 8)
        for s in leaves if jnp.issubdtype(s.dtype, jnp.floating))
    expect_full = sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in leaves if jnp.issubdtype(s.dtype, jnp.floating))
    assert eng.stats.kv_bytes_frac == expect_frac
    assert eng.stats.kv_bytes_full == expect_full
    # the FRAC KV bytes were charged to the recycled flash tier and the
    # per-request reports carry the kv share
    assert "nand-tb" in eng.meter.footprint.by_unit
    assert all(r.detail["kv_frac_bytes"] > 0 for r in eng.reports.values())
    # frac-cache tokens stay close to the full-precision engine's
    eng_full = ServeEngine(mcfg, params, max_batch=2)
    eng_full.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    eng_full.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=4)
    res_full = eng_full.run()
    assert set(res) == set(res_full)


@pytest.mark.parametrize("arch,kbits", [
    ("llama3.2-3b", None),        # dense attention, per-seq positions
    ("llama3.2-3b", 8),           # slot-granular FRAC KV stays per-lane
    ("rwkv6-1.6b", None),         # state freeze at each lane's length
])
def test_serve_ragged_parity(arch, kbits):
    """A mixed-length bucket (one shared prefill, right-padded) must be
    bit-identical to serving every request alone — greedy, same params,
    per-request max_new respected."""
    from repro.models import model as m
    mcfg = get_tiny(arch)
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(2, 12, dtype=np.int32),
               np.arange(3, 10, dtype=np.int32)]
    max_new = [3, 6, 5]
    eng = ServeEngine(mcfg, params, max_batch=4, kv_frac_kbits=kbits)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
    batched = eng.run()
    assert eng.stats.prefills == 1          # one ragged bucket
    for rid, p, n in zip(rids, prompts, max_new):
        solo = ServeEngine(mcfg, params, max_batch=1, kv_frac_kbits=kbits)
        sr = solo.submit(p, max_new_tokens=n)
        assert solo.run()[sr] == batched[rid], (arch, kbits, rid)
        assert len(batched[rid]) == n


def test_serve_eos_and_per_request_max_new_early_exit():
    """EOS / per-request max_new kill lanes inside the scanned loop and
    the loop exits the moment every lane is dead."""
    from repro.models import model as m
    mcfg = get_tiny(ARCH)
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    probe = ServeEngine(mcfg, params, max_batch=1)
    pr = probe.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    ref = probe.run()[pr]
    eos = ref[-1]
    want = ref[: ref.index(eos) + 1]         # truncate at first EOS
    eng = ServeEngine(mcfg, params, max_batch=2, eos_id=eos)
    r1 = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    r2 = eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=2)
    res = eng.run()
    assert res[r1] == want
    assert len(res[r2]) <= 2
    # the loop ran only as long as the longest-lived lane needed
    longest = max(len(res[r1]), len(res[r2]))
    assert eng.stats.decode_steps <= longest
    assert eng.stats.tokens == len(res[r1]) + len(res[r2])


def test_serve_decode_is_device_resident(monkeypatch):
    """Exactly one host transfer per bucket in the decode phase, and the
    decode phase lowers to a single while_loop (tokens never bounce
    through Python between steps)."""
    from repro.models import model as m
    from repro.serve.engine import build_decode_loop
    mcfg = get_tiny(ARCH)
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    eng = ServeEngine(mcfg, params, max_batch=2)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=6)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    res = eng.run()
    assert all(len(v) == 6 for v in res.values())
    assert eng.stats.prefills == 1
    assert len(calls) == 1                  # one transfer for the bucket
    assert eng.stats.host_syncs == 1
    # jaxpr: the whole multi-token decode is one while primitive
    loop = build_decode_loop(mcfg, out_cap=6)
    aparams = m.abstract_params(mcfg)
    acache = m.abstract_cache(mcfg, 2, 14)
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(loop)(aparams, acache, vec, vec, vec)
    assert "while" in str(jaxpr)


def test_serve_paged_decode_is_device_resident(monkeypatch):
    """The paged super-bucket keeps the host-sync lock: exactly one
    transfer for the whole trace even though admission happens
    mid-decode, and the paged loop still lowers to a while primitive
    (allocation, freeing and slot refill never bounce through Python)."""
    from repro.models import model as m
    from repro.serve import paging
    from repro.serve.engine import build_paged_decode_loop
    mcfg = get_tiny(ARCH)
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    eng = ServeEngine(mcfg, params, max_batch=2, paged=True, page_size=4)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=6)
    eng.submit(np.arange(3, 8, dtype=np.int32), max_new_tokens=4)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    res = eng.run()
    assert len(res) == 3 and eng.stats.admissions == 1
    assert eng.stats.prefills == 1
    assert len(calls) == 1                  # one transfer, whole trace
    assert eng.stats.host_syncs == 1
    # jaxpr: the decode+admission phase is a device-resident while loop
    loop = build_paged_decode_loop(mcfg, out_cap=4, page_size=4)
    plan = paging.plan_pages([8, 8, 5], [4, 4, 4], 2, 4)
    aparams = m.abstract_params(mcfg)
    apool = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        m.paged_pool_specs(mcfg, plan.n_pages, 4),
        is_leaf=lambda x: hasattr(x, "dims"))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    jaxpr = jax.make_jaxpr(loop)(
        aparams, apool, i32(2, plan.max_pages), i32(plan.n_pages), i32(),
        i32(2), i32(2), i32(1), i32(1), i32(1, plan.max_pages), i32(3))
    assert "while" in str(jaxpr)


def test_serve_ttft_from_submit_and_queue_drain():
    """TTFT is measured from each request's own submit time, and
    completed requests drain out of the pending queue (sustained load
    stays O(pending) with results accumulating in the returned map)."""
    import time as _time
    from repro.models import model as m
    mcfg = get_tiny(ARCH)
    params = m.init_params(mcfg, jax.random.PRNGKey(0))
    eng = ServeEngine(mcfg, params, max_batch=4)
    r1 = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
    _time.sleep(0.05)
    r2 = eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=2)
    res = eng.run()
    assert set(res) == {r1, r2}
    assert len(eng.stats.ttft_s) == 2
    # r1 waited in queue 50 ms longer than r2 before the shared bucket
    assert eng.stats.ttft_s[0] >= eng.stats.ttft_s[1] + 0.04
    assert eng._pending == []               # completed requests drained
    # new submissions join free slots at the next bucket boundary and
    # the results map keeps its shape (all completed rids)
    r3 = eng.submit(np.arange(3, 11, dtype=np.int32), max_new_tokens=2)
    res2 = eng.run()
    assert set(res2) == {r1, r2, r3}
    assert res2[r1] == res[r1]
    assert eng.stats.prefills == 2 == eng.stats.host_syncs


def test_serve_under_mesh_subprocess(subproc):
    """Sharded serving (params via the weight rule, cache via the
    decode-cache rule, loop vectors via serve_loop_spec) reproduces the
    unsharded outputs."""
    out = subproc("""
import jax, numpy as np
from repro.configs import get_tiny
from repro.launch.mesh import make_host_mesh
from repro.models import model
from repro.serve.engine import ServeEngine

mcfg = get_tiny("llama3.2-3b")
params = model.init_params(mcfg, jax.random.PRNGKey(0))
def serve(mesh):
    eng = ServeEngine(mcfg, params, max_batch=2, mesh=mesh)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    eng.submit(np.arange(2, 10, dtype=np.int32), max_new_tokens=4)
    return eng.run()
plain = serve(None)
sharded = serve(make_host_mesh(2, 1))
assert plain == sharded, (plain, sharded)
print("MESH_SERVE_OK", sorted(plain))
""", n_devices=2)
    assert "MESH_SERVE_OK" in out


def test_elastic_reshard_subprocess(subproc):
    """Save on a (2,2) mesh, restore on (4,1) — elastic restart."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.configs import get_tiny
from repro.models import model
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import plan_remesh, reshard_state
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.launch.mesh import make_host_mesh

cfg = get_tiny("llama3.2-3b")
root = tempfile.mkdtemp()
mesh_a = make_host_mesh(2, 2)
params = model.init_params(cfg, jax.random.PRNGKey(0))
opt = init_opt_state(params, AdamWConfig())
m = CheckpointManager(root, mode="exact")
m.save(3, {"params": params, "opt": opt}, extra={"data_step": 3})

mesh_b = make_host_mesh(4, 1)
plan = plan_remesh(cfg, mesh_b)
p2, o2, extra = reshard_state(m, cfg, mesh_b, step=3)
assert extra["data_step"] == 3
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
    assert (np.asarray(a) == np.asarray(b)).all()
print("RESHARD_OK", plan["mesh"])
""", n_devices=4)
    assert "RESHARD_OK" in out


def test_data_pipeline_stateless_determinism():
    cfg = get_tiny(ARCH)
    s1 = DataStream(cfg, 2, 16, start_step=5)
    s2 = DataStream(cfg, 2, 16).seek(5)
    b1, b2 = next(s1), next(s2)
    assert (np.asarray(b1["tokens"]) == np.asarray(b2["tokens"])).all()
    direct = make_batch(cfg, 2, 16, step=5)
    assert (np.asarray(direct["tokens"]) == np.asarray(b1["tokens"])).all()
    # different steps differ
    b3 = next(s1)
    assert not (np.asarray(b3["tokens"]) == np.asarray(b1["tokens"])).all()


def test_data_tokens_in_range():
    for arch in ("llama3.2-3b", "whisper-medium", "pixtral-12b"):
        cfg = get_tiny(arch)
        b = make_batch(cfg, 2, 32, step=0)
        toks = np.asarray(b["tokens"])
        assert toks.min() >= 0 and toks.max() < cfg.vocab_size


def test_ese_estimates_a_dryrun_record():
    rec = {
        "roofline": {
            "t_compute_s": 0.4, "t_memory_s": 0.9, "t_collective_s": 0.2,
            "flops_per_device": 8e13, "hbm_bytes_per_device": 7e11,
            "collective_bytes_per_device": 1e10,
            "step_time_bound_s": 0.9, "chips": 256,
        },
    }
    with pytest.warns(DeprecationWarning):   # legacy dict adapter
        est = estimator.estimate_task(rec, n_steps=100,
                                      net_demand_quantile=0.2)
    assert est.latency_s == pytest.approx(90.0)
    assert est.operational_j > 0 and est.embodied_j > 0
    assert est.bill_usd > 0
    # recycled opt-in lowers the bill
    with pytest.warns(DeprecationWarning):
        est_r = estimator.estimate_task(rec, n_steps=100,
                                        net_demand_quantile=0.2,
                                        recycled_optin=True)
    assert est_r.bill_usd < est.bill_usd
    # the typed front door agrees with the adapter
    from repro.core.ese import RooflineRecord, TaskSpec, estimate
    typed = estimate(RooflineRecord.from_cell(rec),
                     TaskSpec(n_steps=100, net_demand_quantile=0.2))
    assert typed.bill_usd == pytest.approx(est.bill_usd)


def test_shapes_registry_complete():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    from repro.configs import ARCH_IDS, get_config, shape_applicable

    cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    assert len(cells) == 44            # the assigned 40-cell grid + step3
    runnable = [c for c in cells
                if shape_applicable(get_config(c[0]), SHAPES[c[1]])]
    # 8 full-attention archs (step3 among them) skip long_500k
    assert len(runnable) == 44 - 8


def test_amoeba_engine_dispatch():
    from repro.core.amoeba.engines import Engine, dispatch

    assert Engine.MPE in dispatch("ntt")
    assert Engine.CPE in dispatch("sha3")
    assert dispatch("conv") == (Engine.MPE,)
    with pytest.raises(ValueError, match=r"valid: conv \| ntt \| sha3"):
        dispatch("unknown")


def test_amoeba_primitives():
    import jax.numpy as jnp
    from repro.core.amoeba import engines, trg

    x = jnp.arange(128, dtype=jnp.int32)
    for s in (1, 7, 64):
        assert (engines.cyclic_permute_mvm(x, s).astype(jnp.int32)
                == jnp.roll(x, s)).all()
    a = jnp.asarray([0, 1, 123456, 2**30], jnp.uint32)
    b = jnp.asarray([0, 2, 654321, 12345], jnp.uint32)
    assert (engines.ape_add(a, b) == a + b).all()
    assert (engines.cpe_logic(a, b, "xor") == (a ^ b)).all()
    assert int(engines.amoeba_mul(jnp.asarray([7], jnp.uint32), 12289)[0]) \
        == 7 * 12289
    # LUT: associative match
    keys = jnp.asarray([5, 1, 5], jnp.int32)
    tk = jnp.asarray([1, 5], jnp.int32)
    tv = jnp.asarray([[10.0], [20.0]], jnp.float32)
    out = engines.ape_lut(keys, tk, tv)
    assert np.allclose(np.asarray(out)[:, 0], [20.0, 10.0, 20.0])
    # TRG bias correction
    k = jax.random.PRNGKey(0)
    raw = trg.bias(trg.biased_bits(k, 48))
    cor = trg.bias(trg.counter_corrected_bits(k, 48))
    assert abs(cor - 0.5) < abs(raw - 0.5)
    assert abs(cor - 0.5) < 0.02
