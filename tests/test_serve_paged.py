"""Differential harness for the paged serve engine.

Three layers of lock:

1. **Allocator properties** (hypothesis): arbitrary
   admit/grow/finish interleavings driven through the *same* jnp
   primitives the jitted decode loop uses (``paging.alloc_pages`` /
   ``free_lane_pages``) preserve free-list conservation, never alias a
   page across live sequences, and never hand out the trash page.
2. **Differential serving**: a paged mixed-length bucket is
   bit-identical per request to the PR 4 contiguous engine AND to solo
   serving — llama with and without FRAC KV, rwkv via the documented
   contiguous fallback.
3. **In-loop admission oracle**: the same request trace replayed
   through the bucket-boundary engine yields identical per-request
   token streams, while the paged super-bucket uses strictly fewer
   host syncs and strictly less peak resident KV than the contiguous
   bucket-max layout.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_tiny
from repro.models import model
from repro.serve import paging
from repro.serve.engine import ServeEngine

ARCH = "llama3.2-3b"


def _params(arch=ARCH):
    return model.init_params(get_tiny(arch), jax.random.PRNGKey(0))


def _serve(mcfg, params, prompts, max_new, **kw):
    eng = ServeEngine(mcfg, params, **kw)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
    res = eng.run()
    return eng, [res[r] for r in rids]


# ---------------------------------------------------------------------------
# 1. page-allocator property suite
# ---------------------------------------------------------------------------


class _AllocDriver:
    """Host mirror of the in-loop allocator: one page table, the same
    stack primitives, plus a model of what the engine guarantees (a
    lane never grows past its horizon, the pool is sized for the
    no-reuse worst case, so the stack cannot underflow)."""

    def __init__(self, n_lanes: int, max_pages: int):
        self.n_lanes, self.max_pages = n_lanes, max_pages
        self.n_pages = 1 + n_lanes * max_pages        # +1: trash page 0
        self.pt = jnp.full((n_lanes, max_pages), -1, jnp.int32)
        self.fs = jnp.zeros((self.n_pages,), jnp.int32)
        self.fs = self.fs.at[: self.n_pages - 1].set(
            jnp.arange(1, self.n_pages, dtype=jnp.int32))
        self.ft = jnp.asarray(self.n_pages - 1, jnp.int32)

    def grow(self, lane: int) -> bool:
        col = int((np.asarray(self.pt[lane]) >= 0).sum())
        if col >= self.max_pages:
            return False                               # lane at horizon
        need = jnp.zeros((self.n_lanes,), bool).at[lane].set(True)
        cols = jnp.full((self.n_lanes,), col, jnp.int32)
        self.pt, self.ft, m = paging.alloc_pages(
            self.pt, self.fs, self.ft, need, cols)
        assert int(m) == 1
        return True

    def finish(self, lane: int):
        row, self.fs, self.ft, _ = paging.free_lane_pages(
            self.pt[lane], self.fs, self.ft, jnp.asarray(True))
        self.pt = self.pt.at[lane].set(row)

    def check(self):
        pt = np.asarray(self.pt)
        ft = int(self.ft)
        live = pt[pt >= 0]
        free = np.asarray(self.fs)[:ft]
        # never the trash page, never out of range
        assert (live > 0).all() and (live < self.n_pages).all()
        assert (free > 0).all() and (free < self.n_pages).all()
        # no page aliased across live rows, none both live and free
        assert len(set(live.tolist())) == live.size, "double allocation"
        assert len(set(free.tolist())) == free.size, "double free"
        assert not set(live.tolist()) & set(free.tolist())
        # conservation: every non-trash page is live xor free
        assert ft + live.size == self.n_pages - 1
        assert set(live.tolist()) | set(free.tolist()) \
            == set(range(1, self.n_pages))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 4), st.integers(1, 5))
def test_page_allocator_properties(seed, n_lanes, max_pages):
    import random

    rnd = random.Random(seed)
    drv = _AllocDriver(n_lanes, max_pages)
    drv.check()
    for _ in range(40):
        lane = rnd.randrange(n_lanes)
        if rnd.random() < 0.65:
            drv.grow(lane)
        else:
            drv.finish(lane)
        drv.check()
    for lane in range(n_lanes):                       # drain everything
        drv.finish(lane)
    drv.check()
    assert int(drv.ft) == drv.n_pages - 1             # all pages returned


def test_alloc_assigns_in_lane_order_and_free_roundtrips():
    drv = _AllocDriver(3, 2)
    need = jnp.asarray([True, False, True])
    cols = jnp.zeros((3,), jnp.int32)
    pt, ft, m = paging.alloc_pages(drv.pt, drv.fs, drv.ft, need, cols)
    assert int(m) == 2 and int(ft) == int(drv.ft) - 2
    got = np.asarray(pt)[:, 0]
    assert got[1] == -1 and got[0] != got[2] and (got[[0, 2]] > 0).all()
    # freeing a lane returns exactly its pages, clears the row
    row, fs, ft2, n = paging.free_lane_pages(
        pt[0], drv.fs, ft, jnp.asarray(True))
    assert int(n) == 1 and int(ft2) == int(ft) + 1
    assert (np.asarray(row) == -1).all()
    assert int(np.asarray(fs)[int(ft)]) == int(got[0])
    # disabled free is a no-op
    row3, _, ft3, n3 = paging.free_lane_pages(
        pt[2], drv.fs, ft, jnp.asarray(False))
    assert int(n3) == 0 and int(ft3) == int(ft)
    assert (np.asarray(row3) == np.asarray(pt[2])).all()


def test_plan_pages_layout():
    plan = paging.plan_pages([5, 17, 3], [4, 8, 1], 2, page_size=4)
    # prompt pages 2+5+1 = 8; growth (horizon - prompt) = [1, 2, 0],
    # top-2 = 3 -> P = 1 + 8 + 3 (tight: only 2 lanes decode at once)
    assert plan.n_pages == 12 and plan.max_pages == 7
    assert plan.page_table.shape == (2, 7)
    assert plan.staged_pt.shape == (1, 7)
    assert list(plan.prompt_pages) == [2, 5, 1]
    ids = np.concatenate([plan.page_table[plan.page_table > 0],
                          plan.staged_pt[plan.staged_pt > 0]])
    assert sorted(ids.tolist()) == list(range(1, 9))   # prompt pages
    assert plan.free_top == plan.n_pages - 1 - ids.size
    free = plan.free_stack[: plan.free_top]
    assert sorted(free.tolist()) == list(range(9, 12))
    # pow2 rounding only adds spare pages to the free stack
    p2 = paging.plan_pages([5, 17, 3], [4, 8, 1], 2, page_size=4, pow2=True)
    assert p2.n_pages == 16 and p2.max_pages == 8
    assert p2.free_top == p2.n_pages - 1 - ids.size
    assert (p2.page_table[:, :7] == plan.page_table).all()
    # provisioning is tight: deeper queues stop paying the no-reuse
    # worst case (10 one-page prompts behind 2 lanes: 11+2, not 21)
    deep = paging.plan_pages([2] * 10, [8] * 10, 2, page_size=4)
    assert deep.n_pages == 1 + 10 + 2 * 2
    assert deep.n_pages < 1 + 10 * 3


def test_pool_scatter_routes_pad_rows_to_nowhere():
    full_table = np.asarray([[1, 2, -1], [3, -1, -1]], np.int32)
    pi, oi = paging.pool_scatter_indices(
        full_table, [6, 2], seq_len=8, n_pages=4, page_size=4)
    pi, oi = pi.reshape(2, 8), oi.reshape(2, 8)
    assert pi[0, :4].tolist() == [1] * 4 and pi[0, 4:6].tolist() == [2, 2]
    assert pi[0, 6:].tolist() == [4, 4]               # pad rows dropped
    assert pi[1, :2].tolist() == [3, 3] and (pi[1, 2:] == 4).all()
    assert oi[0].tolist() == [0, 1, 2, 3, 0, 1, 2, 3]
    pool = jnp.zeros((1, 4, 4, 1), jnp.float32)
    leaf = jnp.arange(16, dtype=jnp.float32).reshape(1, 2, 8, 1, 1)
    filled = paging.fill_pool(pool, leaf, jnp.asarray(pi.reshape(-1)),
                              jnp.asarray(oi.reshape(-1)))
    got = np.asarray(filled)[0, :, :, 0]
    assert got[1].tolist() == [0, 1, 2, 3]            # lane 0 page 0
    assert got[2].tolist() == [4, 5, 0, 0]            # lane 0 page 1 head
    assert got[3].tolist() == [8, 9, 0, 0]            # lane 1 page 0 head
    assert (got[0] == 0).all()                        # trash page untouched


def test_gather_pages_restores_logical_order():
    from repro.models.common import gather_pages

    pool = jnp.arange(2 * 4 * 2, dtype=jnp.float32).reshape(2, 4, 2, 1)
    table = jnp.asarray([[3, 1], [2, -1]], jnp.int32)
    got = np.asarray(gather_pages(pool, table, 0))[:, :, 0]
    assert got[0].tolist() == [6.0, 7.0, 2.0, 3.0]
    assert got[1, :2].tolist() == [4.0, 5.0]          # tail rows are masked
    got = np.asarray(gather_pages(pool, table, 1))[:, :, 0]
    assert got[0].tolist() == [14.0, 15.0, 10.0, 11.0]  # the second layer


# ---------------------------------------------------------------------------
# 2. differential: paged == contiguous == solo
# ---------------------------------------------------------------------------

PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(2, 12, dtype=np.int32),
           np.arange(3, 10, dtype=np.int32)]
MAX_NEW = [3, 6, 5]


@pytest.mark.parametrize("kbits", [None, 8])
def test_paged_bit_identical_to_contiguous_and_solo(kbits):
    mcfg = get_tiny(ARCH)
    params = _params()
    contig, res_c = _serve(mcfg, params, PROMPTS, MAX_NEW,
                           max_batch=4, kv_frac_kbits=kbits)
    eng, res_p = _serve(mcfg, params, PROMPTS, MAX_NEW, max_batch=4,
                        kv_frac_kbits=kbits, paged=True, page_size=4)
    assert eng.paged and eng.stats.prefills == 1
    assert res_p == res_c, f"paged vs contiguous diverged (kbits={kbits})"
    for p, n, toks in zip(PROMPTS, MAX_NEW, res_p):
        solo, (ref,) = _serve(mcfg, params, [p], [n], max_batch=1,
                              kv_frac_kbits=kbits)
        assert toks == ref, f"paged vs solo diverged (kbits={kbits})"
        assert len(toks) == n


def test_paged_page_size_invariance():
    """The page size is a layout knob, never a numerics knob."""
    mcfg = get_tiny(ARCH)
    params = _params()
    outs = []
    for ps in (2, 4, 16):
        _, res = _serve(mcfg, params, PROMPTS, MAX_NEW, max_batch=4,
                        paged=True, page_size=ps)
        outs.append(res)
    assert outs[0] == outs[1] == outs[2]


def test_paged_falls_back_for_state_space_families():
    """rwkv has an O(1) recurrent state — nothing to page.  The flag
    degrades to the contiguous engine with identical results, and the
    silent downgrade is surfaced as a UserWarning."""
    mcfg = get_tiny("rwkv6-1.6b")
    params = _params("rwkv6-1.6b")
    with pytest.warns(UserWarning, match="falling back"):
        eng_p, res_p = _serve(mcfg, params, PROMPTS, MAX_NEW, max_batch=4,
                              paged=True)
    eng_c, res_c = _serve(mcfg, params, PROMPTS, MAX_NEW, max_batch=4)
    assert not eng_p.paged
    assert res_p == res_c
    assert eng_p.stats.admissions == 0 and eng_p.stats.kv_pages_peak == 0


def test_paged_eos_early_exit_and_doa_requests():
    """EOS kills a lane mid-loop (pages freed, next request admitted)
    and a max_new=1 request completes through staging without ever
    decoding."""
    mcfg = get_tiny(ARCH)
    params = _params()
    probe, (ref,) = _serve(mcfg, params, [np.arange(1, 9, dtype=np.int32)],
                           [8], max_batch=1)
    eos = ref[-1]
    want = ref[: ref.index(eos) + 1]
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(2, 10, dtype=np.int32),
               np.arange(3, 11, dtype=np.int32)]
    eng, (o1, o2, o3) = _serve(mcfg, params, prompts, [8, 2, 1],
                               max_batch=1, paged=True, page_size=4,
                               eos_id=eos)
    assert o1 == want
    assert len(o2) <= 2 and len(o3) == 1
    assert eng.stats.host_syncs == 1          # one super-bucket
    assert eng.stats.admissions == 2          # both refills in-loop
    assert eng.stats.tokens == len(o1) + len(o2) + len(o3)


# ---------------------------------------------------------------------------
# 3. in-loop admission oracle vs the bucket-boundary engine
# ---------------------------------------------------------------------------


def test_in_loop_admission_oracle():
    """Replay one trace through both engines: identical per-request
    streams, strictly fewer host syncs (one super-bucket vs one sync
    per bucket), and strictly less peak resident KV than bucket-max."""
    mcfg = get_tiny(ARCH)
    params = _params()
    rng = np.random.default_rng(7)
    plens = [4, 6, 48, 5, 8, 6]                # skewed: one long anchor
    prompts = [rng.integers(1, mcfg.vocab_size, p).astype(np.int32)
               for p in plens]
    max_new = [8, 6, 8, 4, 8, 5]
    contig, res_c = _serve(mcfg, params, prompts, max_new, max_batch=2)
    paged, res_p = _serve(mcfg, params, prompts, max_new, max_batch=2,
                          paged=True, page_size=4, stage_depth=8)
    assert res_p == res_c                      # identical token streams
    assert [len(t) for t in res_p] == max_new
    # admission happened inside the loop, not at bucket boundaries
    assert paged.stats.host_syncs == 1 == paged.stats.prefills
    assert contig.stats.host_syncs == 3 == contig.stats.prefills
    assert paged.stats.host_syncs < contig.stats.host_syncs
    assert paged.stats.admissions == len(prompts) - paged.max_batch
    # paged peak strictly below the contiguous bucket-max layout
    assert 0 < paged.stats.kv_bytes_peak < contig.stats.kv_bytes_peak
    # conservation held end-to-end: the loop's high-water mark can
    # never exceed the no-reuse worst case the plan provisioned
    assert paged.stats.kv_pages_peak <= sum(
        paging.pages_for(p + m, 4) for p, m in zip(plens, max_new))


# ---------------------------------------------------------------------------
# 4. flash-oversubscribed differential: every recovery stage bit-identical
# ---------------------------------------------------------------------------


def _tier(events=(), seed=1):
    from repro.core.frac.wear import RecycledChip
    from repro.serve.faults import FaultConfig
    from repro.serve.flash_tier import FlashTier

    return FlashTier(RecycledChip(n_blocks=64, seed=seed),
                     faults=FaultConfig(seed=seed, rber_scale=0.0,
                                        events=tuple(events)))


OVERSUB_PROMPTS = [np.arange(1, 6, dtype=np.int32),
                   np.arange(2, 12, dtype=np.int32),
                   np.arange(3, 10, dtype=np.int32),
                   np.arange(4, 11, dtype=np.int32),
                   np.arange(5, 14, dtype=np.int32)]
OVERSUB_MAX_NEW = [3, 6, 5, 4, 6]


@pytest.mark.parametrize("kbits", [None, 8])
def test_flash_oversub_bit_identical(kbits):
    """Oversubscribed waves (spill -> flash -> fault-in) reproduce the
    non-oversubscribed paged engine and solo serving token-for-token —
    with and without FRAC KV — including a lane whose pages are LOST
    on flash (recovery stage 3: re-prefill)."""
    mcfg = get_tiny(ARCH)
    params = _params()
    kw = dict(max_batch=2, paged=True, page_size=4, stage_depth=8,
              kv_frac_kbits=kbits)
    base, res_b = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW, **kw)
    quiet, res_q = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW,
                          flash=_tier(), **kw)
    assert res_q == res_b, f"oversubscribed diverged (kbits={kbits})"
    assert quiet.stats.oversub_waves >= 2
    assert quiet.stats.spills > 0
    assert quiet.stats.faultins == quiet.stats.spills
    # deepest ladder stage: a page lost on flash, lane re-prefilled
    from repro.serve.faults import FaultEvent

    lost, res_l = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW,
                         flash=_tier(events=(
                             FaultEvent("bit_flip", at=1, severity=50.0),)),
                         **kw)
    assert res_l == res_b, f"re-prefill recovery diverged (kbits={kbits})"
    assert lost.stats.reprefills >= 1 and lost.stats.reprefill_tokens > 0
    # vs solo, spot-checked (paged == solo is locked exhaustively above)
    for i in (1, 4):
        _, (ref,) = _serve(mcfg, params, [OVERSUB_PROMPTS[i]],
                           [OVERSUB_MAX_NEW[i]], max_batch=1,
                           kv_frac_kbits=kbits)
        assert res_q[i] == ref


@pytest.mark.parametrize("sev,stage", [(0.5, "ecc"), (2.0, "retry")])
def test_flash_oversub_mid_ladder_stages(sev, stage):
    """Forced faults that resolve *within* the flash tier (ECC budget /
    retry-read) never reach the token stream."""
    from repro.serve.faults import FaultEvent

    mcfg = get_tiny(ARCH)
    params = _params()
    kw = dict(max_batch=2, paged=True, page_size=4, stage_depth=8)
    base, res_b = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW, **kw)
    eng, res = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW,
                      flash=_tier(events=(
                          FaultEvent("bit_flip", at=1, severity=sev),
                          FaultEvent("bit_flip", at=2, severity=sev))),
                      **kw)
    assert res == res_b
    if stage == "ecc":
        assert eng.stats.ecc_corrected >= 2 and eng.stats.retry_reads == 0
    else:
        assert eng.stats.retry_reads >= 2
    assert eng.stats.reprefills == 0


def test_paged_solo_degenerates_to_single_lane():
    """B=1, no staged requests: the paged loop is just a solo decode
    with a page table — results identical, one sync."""
    mcfg = get_tiny(ARCH)
    params = _params()
    solo, (ref,) = _serve(mcfg, params, [PROMPTS[1]], [6], max_batch=1)
    eng, (got,) = _serve(mcfg, params, [PROMPTS[1]], [6], max_batch=1,
                         paged=True, page_size=4)
    assert got == ref
    assert eng.stats.admissions == 0 and eng.stats.host_syncs == 1


# ---------------------------------------------------------------------------
# 5. fused paged-attention kernel: kernel == gather oracle == contiguous
# ---------------------------------------------------------------------------


def _rand_paged_fixture(seed, B, ps, dtype=jnp.float32, L=3):
    """Random stacked pools ``(L, P, ps, K*hd)`` (every layer its own
    contents) + valid page tables + ragged positions: every lane's
    allocated prefix covers its own ``pos`` (the invariant the engine's
    allocator maintains), page ids distinct across lanes, -1 tails."""
    rng = np.random.default_rng(seed)
    mp = int(rng.integers(2, 6))
    H, K, hd = 4, 2, 8
    P = B * mp + 1                               # + trash page 0
    q = jnp.asarray(rng.standard_normal((B, H, hd)), dtype)
    pk = jnp.asarray(rng.standard_normal((L, P, ps, K * hd)), dtype)
    pv = jnp.asarray(rng.standard_normal((L, P, ps, K * hd)), dtype)
    ids = rng.permutation(np.arange(1, P))
    table = np.full((B, mp), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    used = 0
    for b in range(B):
        n_alloc = int(rng.integers(1, mp + 1))
        table[b, :n_alloc] = ids[used:used + n_alloc]
        used += n_alloc
        pos[b] = int(rng.integers(0, n_alloc * ps))
    return q, pk, pv, jnp.asarray(table), jnp.asarray(pos)


def _oracle_attn(q, pk, pv, table, pos, layer):
    """Gather + common.attention over layer ``layer``, sliced out of
    the stack on the host so the oracle shares no layer indexing with
    the paths under test."""
    from repro.models.common import attention, gather_pages

    B, H, hd = q.shape
    kv = [gather_pages(jnp.asarray(np.asarray(p)[layer:layer + 1]), table,
                       0).reshape(B, -1, p.shape[-1] // hd, hd)
          for p in (pk, pv)]
    return attention(q[:, None], *kv, causal=False,
                     kv_valid_len=pos + 1, q_positions=pos[:, None])[:, 0]


def test_paged_attn_modes_agree_and_match_oracle():
    """The jnp page walk and the Pallas kernel (interpret) are
    bit-identical to each other — same per-page fp32 math — and agree
    with the gather + common.attention oracle to rounding (the oracle
    reduces in a different order; see kernels/paged_attn)."""
    from repro.kernels.paged_attn import ops as pops

    q, pk, pv, table, pos = _rand_paged_fixture(0, B=3, ps=4)
    o_jnp = pops.paged_attention(q, pk, pv, table, pos, 1, mode="jnp")
    o_int = pops.paged_attention(q, pk, pv, table, pos, 1,
                                 mode="pallas_interpret")
    assert jnp.array_equal(o_jnp, o_int)
    oracle = _oracle_attn(q, pk, pv, table, pos, 1)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="expected one of"):
        pops.paged_attention(q, pk, pv, table, pos, 1, mode="cuda")


@pytest.mark.parametrize("mode", ["jnp", "pallas_interpret"])
def test_paged_attn_reads_the_given_layer(mode):
    """The read takes layer ``l`` of the stacked pool in place: against
    the oracle on each of three layers with different contents, and a
    read of any other layer is far off (so a wrong-layer DMA or gather
    fails here)."""
    from repro.kernels.paged_attn import ops as pops

    q, pk, pv, table, pos = _rand_paged_fixture(5, B=3, ps=4, L=3)
    oracles = [np.asarray(_oracle_attn(q, pk, pv, table, pos, l))
               for l in range(3)]
    read = jax.jit(lambda l: pops.paged_attention(q, pk, pv, table, pos, l,
                                                  mode=mode))
    for l in range(3):
        got = np.asarray(read(jnp.int32(l)))
        np.testing.assert_allclose(got, oracles[l], rtol=1e-5, atol=1e-6)
        for other in set(range(3)) - {l}:
            assert np.abs(got - oracles[other]).max() > 0.1


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10**6), st.sampled_from([2, 4]),
       st.integers(1, 3), st.integers(0, 2))
def test_paged_attn_property_vs_oracle(seed, ps, B, layer):
    """Property lock over random valid page tables / ragged positions
    and layers: fused walk == gather oracle (rounding), jnp ==
    interpret (bits)."""
    from repro.kernels.paged_attn import ops as pops

    q, pk, pv, table, pos = _rand_paged_fixture(seed, B=B, ps=ps)
    o_jnp = pops.paged_attention(q, pk, pv, table, pos, layer, mode="jnp")
    o_int = pops.paged_attention(q, pk, pv, table, pos, layer,
                                 mode="pallas_interpret")
    assert jnp.array_equal(o_jnp, o_int)
    oracle = _oracle_attn(q, pk, pv, table, pos, layer)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("ps", [2, 4, 16])
def test_trash_page_poison_is_masked(ps):
    """gather_pages documents unallocated entries as "garbage but
    finite, always masked" — lock it adversarially: a NaN/inf-poisoned
    trash page must leave both the gather path and the fused kernel
    bit-identical to the clean pool (a multiplicative mask would leak
    NaN through 0 * nan)."""
    from repro.kernels.paged_attn import ops as pops

    q, pk, pv, table, pos = _rand_paged_fixture(7, B=3, ps=ps)
    clean = {m: pops.paged_attention(q, pk, pv, table, pos, 2, mode=m)
             for m in ("jnp", "pallas_interpret")}
    clean_g = _oracle_attn(q, pk, pv, table, pos, 2)
    pk_p = pk.at[:, 0].set(jnp.nan)
    pv_p = pv.at[:, 0].set(jnp.inf)
    for m, ref in clean.items():
        got = pops.paged_attention(q, pk_p, pv_p, table, pos, 2, mode=m)
        assert jnp.array_equal(got, ref), f"poison leaked through {m}"
    got_g = _oracle_attn(q, pk_p, pv_p, table, pos, 2)
    assert jnp.array_equal(got_g, clean_g), "poison leaked through gather"


# producers allowed to yield a pool-sized array inside the decode loop:
# the loop's parameters and carry, and the in-place row scatter
_POOL_PRODUCERS = {"parameter", "get-tuple-element", "bitcast", "scatter"}


def pool_movers(hlo_text: str, pool_elems: set) -> list:
    """Instructions of an optimized HLO module that produce an array
    with as many elements as the stacked pool or one layer's pool
    (whatever its shape or layout) from anything but the loop's
    parameters, its carry or the row scatter — a fusion counts by the
    opcode of its root.  Each as (name, producer, shape)."""
    inst = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                      r"([\w-]+)\((.*)")
    roots, comp, found = {}, None, []
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head and "=" not in line.split("(")[0]:
            comp = head.group(1)
        m = inst.match(line)
        if m and line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(3)
        if m:
            found.append(m.groups())
    movers = []
    for name, shape, op, rest in found:
        if int(np.prod([int(d) for d in shape.split(",") if d])) \
                not in pool_elems:
            continue
        if op == "fusion":
            op = roots.get(re.search(r"calls=%(\S+?),?\s", rest + " ")
                           .group(1), op)
        if op not in _POOL_PRODUCERS:
            movers.append((name, op, shape))
    return movers


def _layer_scans(jaxpr, weight_shape):
    """Every ``scan`` in a jaxpr, nested ones included, that scans an
    operand of ``weight_shape`` (a stacked layer weight)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            xs = eqn.invars[eqn.params["num_consts"]
                            + eqn.params["num_carry"]:]
            if weight_shape in [v.aval.shape for v in xs]:
                out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _layer_scans(sub, weight_shape)
    return out


@pytest.mark.parametrize("kernel", [False, True])
def test_paged_loop_carries_the_pool_and_never_copies_it(kernel):
    """The layer scan of the paged decode loop carries the stacked pool
    (weights are its only scanned operand), and the compiled loop moves
    no pool: no op outside the loop's parameters, its carry and the
    row scatter yields an array the size of the stack or of one
    layer's pool — no copy, no dynamic-slice or dynamic-update-slice,
    no relayout.  Weights and pool are float32 here: the CPU backend
    widens a bf16 scatter to float32 around its whole operand, a
    CPU-only rewrite (tests/test_tpu_compile.py checks the bf16 loop
    for the chip)."""
    cfg = get_tiny(ARCH)
    B, Q, mp, P, ps = 2, 2, 4, 23, 4       # odd P: pool sizes are unique
    L = cfg.num_layers
    lanes = cfg.num_kv_heads * cfg.head_dim
    from repro.models.common import is_leaf_spec
    from repro.serve.engine import build_paged_decode_loop

    pool = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        model.paged_pool_specs(cfg, P, ps), is_leaf=is_leaf_spec)
    assert {a.shape for a in jax.tree.leaves(pool)} == {(L, P, ps, lanes)}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    loop = build_paged_decode_loop(cfg, kv_kbits=8, out_cap=8, page_size=ps,
                                   paged_kernel=kernel)
    f32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32),
                       model.abstract_params(cfg))
    args = (f32, pool, i32(B, mp), i32(P), i32(),
            i32(B), i32(B), i32(Q), i32(Q), i32(Q, mp), i32(B + Q))
    wq = f32["layers"]["attn_0"]["wq"].shape
    (scan,) = _layer_scans(jax.make_jaxpr(loop)(*args).jaxpr, wq)
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    shape = lambda vs: [v.aval.shape for v in vs]          # noqa: E731
    pool_shape = (L, P, ps, lanes)
    assert shape(scan.invars[nc:nc + nk]).count(pool_shape) == 2
    assert pool_shape not in shape(scan.invars[:nc] + scan.invars[nc + nk:])
    assert shape(scan.outvars[:nk]).count(pool_shape) == 2
    assert pool_shape not in shape(scan.outvars[nk:])
    hlo = loop.lower(*args).compile().as_text()
    assert "scatter" in hlo
    assert pool_movers(hlo, {L * P * ps * lanes, P * ps * lanes}) == []


def test_paged_write_overflow_routes_to_trash():
    """A lane whose position has outrun its page table must write to
    the reserved trash page, NOT clamp into its last allocated page
    (the pre-fix behavior silently corrupted the final page in place)."""
    mcfg = get_tiny(ARCH)
    params = _params()
    ps, n_pages, mp = 4, 8, 2
    rng = np.random.default_rng(3)
    specs = model.paged_pool_specs(mcfg, n_pages, ps)
    from repro.models.common import is_leaf_spec

    pool = jax.tree.map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), jnp.bfloat16),
        specs, is_leaf=is_leaf_spec)
    table = jnp.asarray([[1, 2]], jnp.int32)
    pos = jnp.asarray([mp * ps], jnp.int32)       # one past capacity
    tok = jnp.asarray([5], jnp.int32)
    for kernel in (False, True):
        logits, new_pool = model.decode_step_paged(
            mcfg, params, pool, table, tok, pos, paged_kernel=kernel)
        assert bool(jnp.isfinite(logits).all())
        for name in jax.tree.leaves(
                jax.tree.map(lambda a, b: jnp.array_equal(a[:, 1:], b[:, 1:]),
                             pool, new_pool)):
            assert bool(name), "overflow write corrupted a live page"


@pytest.mark.parametrize("kbits", [None, 8])
def test_paged_kernel_token_identical(kbits):
    """Engine-level lock: the fused kernel reproduces the gather-oracle
    paged engine and solo serving token-for-token, ± FRAC KV.  The
    long prompt anchors a table wider than the walk's chunk, so the
    modeled attention transient (the byte model the CI bench gates)
    must come out strictly lower for the fused read."""
    mcfg = get_tiny(ARCH)
    params = _params()
    prompts = [np.arange(1, 25, dtype=np.int32)] + PROMPTS
    max_new = [8] + MAX_NEW
    kw = dict(max_batch=4, kv_frac_kbits=kbits, paged=True, page_size=4)
    gather_eng, res_g = _serve(mcfg, params, prompts, max_new, **kw)
    kernel_eng, res_k = _serve(mcfg, params, prompts, max_new,
                               paged_kernel=True, **kw)
    assert kernel_eng.paged_kernel
    assert res_k == res_g, f"kernel vs gather diverged (kbits={kbits})"
    _, (ref,) = _serve(mcfg, params, [prompts[0]], [max_new[0]],
                       max_batch=1, kv_frac_kbits=kbits)
    assert res_k[0] == ref
    # the byte model the CI bench gates: fused read < gather read
    assert (kernel_eng.stats.attn_transient_peak
            < gather_eng.stats.attn_transient_peak)


def test_paged_kernel_page_size_invariance():
    mcfg = get_tiny(ARCH)
    params = _params()
    outs = [_serve(mcfg, params, PROMPTS, MAX_NEW, max_batch=4, paged=True,
                   page_size=ps, paged_kernel=True)[1]
            for ps in (2, 4, 16)]
    assert outs[0] == outs[1] == outs[2]


def test_paged_kernel_flash_waves_identical():
    """Oversubscribed flash waves ride the same jitted loop — flipping
    the kernel flag must not change a single token through spill and
    fault-in."""
    mcfg = get_tiny(ARCH)
    params = _params()
    kw = dict(max_batch=2, paged=True, page_size=4, stage_depth=8)
    _, res_b = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW,
                      flash=_tier(), **kw)
    eng, res_k = _serve(mcfg, params, OVERSUB_PROMPTS, OVERSUB_MAX_NEW,
                        flash=_tier(), paged_kernel=True, **kw)
    assert res_k == res_b
    assert eng.stats.oversub_waves >= 2 and eng.stats.spills > 0


def test_paged_kernel_env_override(monkeypatch):
    mcfg = get_tiny(ARCH)
    params = _params()
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "1")
    assert ServeEngine(mcfg, params, paged=True).paged_kernel
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "off")
    assert not ServeEngine(mcfg, params, paged=True).paged_kernel
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "maybe")
    with pytest.raises(ValueError, match="REPRO_PAGED_KERNEL"):
        ServeEngine(mcfg, params, paged=True)
    monkeypatch.delenv("REPRO_PAGED_KERNEL")
    # explicit argument wins over the default; contiguous engines never
    # set the flag (there is no page table to walk)
    assert not ServeEngine(mcfg, params, paged_kernel=True).paged_kernel
