"""Property tests for the AMOEBA engine primitives (core/amoeba/engines).

The seed smoke tests in test_system.py check single point values; these
lock the algebraic contracts the reconfiguration runtime leans on:
``ape_add`` is 2^32 addition, ``amoeba_mul`` is constant multiplication
mod 2^32, ``cyclic_permute_mvm`` is exactly ``jnp.roll`` for any shift
and width, and ``ape_lut`` returns the stored value on a hit and zero
on a miss.  Driven by hypothesis.
"""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.amoeba import engines

MASK32 = np.uint64(0xFFFFFFFF)


def _rng(seed):
    return np.random.default_rng(seed)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 2**31), st.integers(0, 9999))
def test_ape_add_is_mod32_addition(lo, hi, seed):
    a = _rng(seed).integers(0, 2**32, 16, dtype=np.uint32)
    b = _rng(seed + 1).integers(0, 2**32, 16, dtype=np.uint32)
    # mix in the drawn scalars so examples cover carries at both ends
    a = (a + np.uint32(lo % 2**32)).astype(np.uint32)
    b = (b + np.uint32(hi % 2**32)).astype(np.uint32)
    got = np.asarray(engines.ape_add(jnp.asarray(a), jnp.asarray(b)))
    want = ((a.astype(np.uint64) + b.astype(np.uint64)) & MASK32
            ).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**16 - 1), st.integers(0, 9999))
def test_amoeba_mul_is_const_mul_mod32(b_const, seed):
    a = _rng(seed).integers(0, 2**32, 16, dtype=np.uint32)
    got = np.asarray(engines.amoeba_mul(jnp.asarray(a), int(b_const)))
    want = ((a.astype(np.uint64) * np.uint64(b_const)) & MASK32
            ).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 96), st.integers(-200, 200), st.integers(0, 9999))
def test_cyclic_permute_mvm_is_roll(n, shift, seed):
    # values < 2^20 keep the fp32 MVM path exact (docstring contract)
    x = _rng(seed).integers(0, 2**20, n, dtype=np.int32)
    got = np.asarray(engines.cyclic_permute_mvm(jnp.asarray(x), int(shift)))
    np.testing.assert_array_equal(got, np.roll(x, shift))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(0, 9999))
def test_ape_lut_hit_returns_stored_miss_returns_zero(n_entries, seed):
    rng = _rng(seed)
    table_keys = rng.permutation(2**10)[:n_entries].astype(np.int32)
    table_vals = rng.integers(1, 2**15, (n_entries, 3), dtype=np.int32)
    hits = rng.choice(table_keys, 5)
    misses = np.arange(2**10, 2**10 + 4, dtype=np.int32)  # disjoint keys
    out_hit = np.asarray(engines.ape_lut(
        jnp.asarray(hits), jnp.asarray(table_keys), jnp.asarray(table_vals)))
    for q, row in zip(hits, out_hit):
        np.testing.assert_array_equal(
            row, table_vals[np.flatnonzero(table_keys == q)[0]])
    out_miss = np.asarray(engines.ape_lut(
        jnp.asarray(misses), jnp.asarray(table_keys), jnp.asarray(table_vals)))
    np.testing.assert_array_equal(out_miss, np.zeros((4, 3), np.int32))
