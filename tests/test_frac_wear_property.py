"""Property suite for the flash wear / graceful-degradation models
(core/frac/wear.py, core/frac/policy.py) — hypothesis
(integers / sampled_from / binary only).

Locks the model facts the spill tier and the capacity bench lean on:
RBER grows monotonically in both wear and cell states, the 2-state
endurance multiple matches the paper's Fig 2(d) claim, the degradation
ladder only ever steps *down*, and retired blocks are never handed out
by the wear-leveling allocator.
"""
import math

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.frac import wear
from repro.core.frac.policy import DegradationPolicy, erase_block
from repro.kernels.frac_pack import ops as fops

LADDER = list(wear.M_LADDER)


# ---------------------------------------------------------------------------
# rber monotonicity
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.sampled_from(LADDER), st.integers(1, 50_000), st.integers(1, 10_000))
def test_rber_monotone_in_pe_cycles(m, n_pe, extra):
    assert wear.rber(m, n_pe + extra) >= wear.rber(m, n_pe) > 0.0


@settings(max_examples=60, deadline=None)
@given(st.integers(2, 7), st.integers(1, 50_000))
def test_rber_monotone_in_m(m, n_pe):
    # more states per cell = tighter Vth windows = strictly worse RBER
    assert wear.rber(m + 1, n_pe) > wear.rber(m, n_pe)


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(LADDER))
def test_endurance_is_rber_inverse(m):
    # endurance_cycles is exactly where rber crosses the ECC budget
    n = wear.endurance_cycles(m)
    assert wear.rber(m, n) == pytest.approx(wear.ECC_LIMIT, rel=1e-6)
    assert wear.rber(m, 1.01 * n) > wear.ECC_LIMIT


def test_two_state_endurance_ratio_matches_paper():
    # Fig 2(d): a 2-state cell lasts ~10x a TLC-equivalent (m=8)
    assert wear.endurance_ratio(2) == pytest.approx(10.0, rel=0.05)
    rs = [wear.endurance_ratio(m) for m in LADDER]
    assert rs == sorted(rs)          # fewer states, more endurance


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 400))
def test_ladder_only_steps_down(seed, cycles_per_erase):
    import random

    rnd = random.Random(seed)
    blk = wear.FlashBlock(0, pe_cycles=float(rnd.randrange(0, 8000)))
    policy = DegradationPolicy()
    seen = [blk.m]
    for _ in range(200):
        if blk.retired:
            break
        blk.program_erase(float(cycles_per_erase))
        policy.maybe_degrade(blk)
        seen.append(blk.m)
    ranks = [LADDER.index(m) for m in seen]
    assert ranks == sorted(ranks), "ladder stepped up"
    for a, b in zip(ranks, ranks[1:]):
        assert b - a <= 1, "ladder skipped a rung"
    # a block that fell off the last rung is retired, not resurrected
    if blk.retired:
        policy.maybe_degrade(blk)
        assert blk.retired


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(LADDER))
def test_degrade_restores_headroom_or_retires(m):
    policy = DegradationPolicy()
    blk = wear.FlashBlock(0, m=m)
    # wear it just past this rung's headroom threshold
    blk.pe_cycles = 1.01 * wear.N0 * (
        policy.headroom * wear.ECC_LIMIT / wear.rber_base(m)
    ) ** (1.0 / wear.GAMMA)
    stepped = policy.maybe_degrade(blk)
    if m == LADDER[-1]:
        assert not stepped and blk.retired
    else:
        assert stepped and blk.m == LADDER[LADDER.index(m) + 1]
        # one rung down, same wear: back under the budget (the ladder is
        # spaced so a single step restores margin at the threshold)
        assert blk.rber() < wear.ECC_LIMIT


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(4, 32))
def test_retired_blocks_never_selected_for_placement(seed, n_blocks):
    import random

    rnd = random.Random(seed)
    chip = wear.RecycledChip(n_blocks=n_blocks, seed=seed % 1000)
    for b in chip.blocks:
        if rnd.random() < 0.5:
            b.retired = True
    live = [b.block_id for b in chip.blocks if not b.retired]
    got = chip.least_worn(n_blocks)
    assert [b.block_id for b in got if b.retired] == []
    assert len(got) == len(live)
    pe = [b.pe_cycles for b in got]
    assert pe == sorted(pe)          # least-worn first
    for b in chip.blocks:
        if b.retired:
            assert b.capacity_bytes() == 0.0


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 5000), st.sampled_from(LADDER))
def test_erase_block_wears_and_never_gains_capacity(prewear, m):
    blk = wear.FlashBlock(0, pe_cycles=float(prewear), m=m)
    cap = blk.capacity_bytes()
    out = erase_block(blk, DegradationPolicy())
    assert blk.pe_cycles == prewear + 1.0
    assert blk.capacity_bytes() <= cap
    assert out["m"] == blk.m and out["retired"] == blk.retired


# ---------------------------------------------------------------------------
# page-stream codec: spill bytes survive any ladder m
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.binary(min_size=1, max_size=600), st.sampled_from(LADDER))
def test_page_stream_roundtrip_all_ladder_m(data, m):
    alpha, bits, n_cells = fops.page_stream_geometry(len(data), m)
    levels = fops.bytes_to_levels_np(data, m)
    assert levels.shape == (n_cells,) and int(levels.max(initial=0)) < m
    assert fops.levels_to_bytes_np(levels, m, len(data)) == data
    # geometry matches the codec's densest fractional packing for m
    from repro.core.frac.codec import best_alpha, bits_for

    assert alpha == best_alpha(m) and bits == bits_for(m, alpha)
    assert n_cells >= math.ceil(len(data) * 8 * alpha / bits)
