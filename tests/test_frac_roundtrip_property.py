"""Property-based round-trip suite for the FRAC codec fast paths.

Locks down the fractional-width (cross-word carry) pack/unpack and the
fused encode/decode dispatch: every width 1..16 (plus the >16 widths
the cell code emits), odd lengths, and every ``REPRO_FRAC_MODE``
backend must round-trip bit-exactly, with the seed scatter/gather
implementation (``pack_bits_scatter`` / ``unpack_bits_gather``) as the
oracle.  The oracle survives ONLY here and in the benchmark baseline —
the production ``pack_bits``/``unpack_bits`` never scatter (asserted on
the jaxpr below).

Hypothesis drives it with ``integers``/``sampled_from`` strategies.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.frac import codec
from repro.kernels.frac_pack import ops as fops

ALL_WIDTHS = list(range(1, 17))
ENV_BACKENDS = ("jnp", "pallas", "pallas_interpret")  # REPRO_FRAC_MODE values


def _with_env_mode(mode):
    """Set REPRO_FRAC_MODE for the duration of a call-site loop body."""
    class _Ctx:
        def __enter__(self):
            self.old = os.environ.get("REPRO_FRAC_MODE")
            os.environ["REPRO_FRAC_MODE"] = mode
        def __exit__(self, *exc):
            if self.old is None:
                os.environ.pop("REPRO_FRAC_MODE", None)
            else:
                os.environ["REPRO_FRAC_MODE"] = self.old
    return _Ctx()


# --- pack_bits / unpack_bits vs the scatter/gather oracle --------------------


@settings(max_examples=40, deadline=None)
@given(
    bits=st.integers(1, 16),
    n=st.integers(1, 700),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_bits_matches_scatter_oracle(bits, n, seed):
    """Words AND recovered values bit-identical to the seed scatter/
    gather codec for every width 1..16 and odd lengths."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(
        rng.integers(0, 1 << bits, n, dtype=np.int64).astype(np.uint32))
    fast = codec.pack_bits(vals, bits)
    oracle = codec.pack_bits_scatter(vals, bits)
    assert fast.shape == oracle.shape == (-(-(n * bits) // 32),)
    assert (np.asarray(fast) == np.asarray(oracle)).all()
    back = codec.unpack_bits(fast, bits, n)
    assert (np.asarray(back) == np.asarray(vals)).all()
    # cross-check against the seed gather unpack on the same words
    assert (np.asarray(codec.unpack_bits_gather(oracle, bits, n))
            == np.asarray(back)).all()


@pytest.mark.parametrize("bits", ALL_WIDTHS)
def test_pack_bits_never_scatters(bits):
    """`pack_bits_scatter` survives only as the test oracle: the
    production pack jaxpr is scatter-free for every width 1..16."""
    vals = jnp.zeros((321,), jnp.uint32)
    jaxpr = str(jax.make_jaxpr(lambda v: codec.pack_bits(v, bits))(vals))
    assert "scatter" not in jaxpr, f"k={bits} pack still scatters"


@settings(max_examples=15, deadline=None)
@given(
    bits=st.sampled_from([17, 19, 23, 29, 32]),
    n=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_wide_codewords(bits, n, seed):
    """The carry path also covers the >16-bit codewords the cell code
    emits (bits_for(m, α) up to 32), still oracle-exact."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(
        rng.integers(0, 1 << bits, n, dtype=np.int64).astype(np.uint32))
    fast = codec.pack_bits(vals, bits)
    assert (np.asarray(fast) == np.asarray(
        codec.pack_bits_scatter(vals, bits))).all()
    assert (np.asarray(codec.unpack_bits(fast, bits, n))
            == np.asarray(vals)).all()


# --- tensor encode/decode across every REPRO_FRAC_MODE backend ---------------


@settings(max_examples=12, deadline=None)
@given(
    k=st.sampled_from([1, 3, 5, 7, 8, 11, 13, 16]),
    n=st.integers(1, 600),
    seed=st.integers(0, 2**31 - 1),
)
def test_tensor_roundtrip_all_env_backends(k, n, seed):
    """frac_encode_tensor/frac_decode_tensor (codec oracle) vs the
    ops dispatch under every REPRO_FRAC_MODE: words, scales and decoded
    floats bit-identical.  The compiled 'pallas' mode exists only on a
    TPU: on a CPU backend it raises instead of silently rerouting to
    jnp, which this asserts too."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=n) * rng.uniform(0.01, 50), jnp.float32)
    ref = codec.frac_encode_tensor(x, kbits=k)
    ref_dec = np.asarray(codec.frac_decode_tensor(ref))
    for mode in ENV_BACKENDS:
        with _with_env_mode(mode):
            if mode == "pallas" and jax.default_backend() != "tpu":
                with pytest.raises(ValueError, match="interpret"):
                    fops.encode_tensor(x, kbits=k)
                continue
            blob = fops.encode_tensor(x, kbits=k)
            dec = np.asarray(fops.decode_tensor(blob))
        assert (np.asarray(blob["words"])
                == np.asarray(ref["words"])).all(), (k, mode)
        assert (np.asarray(blob["scales"])
                == np.asarray(ref["scales"])).all(), (k, mode)
        assert (dec == ref_dec).all(), (k, mode)


@settings(max_examples=10, deadline=None)
@given(
    k=st.sampled_from([3, 5, 11]),
    rows=st.integers(1, 20),
    cols=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_tensor_roundtrip_2d_shapes_fractional(k, rows, cols, seed):
    """Shape/dtype survive the fractional fast path, and the decode
    error honors the per-block quantizer bound."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    blob = fops.encode_tensor(x, kbits=k)
    back = fops.decode_tensor(blob)
    assert back.shape == x.shape and back.dtype == x.dtype
    scales = np.asarray(blob["scales"])
    bound = scales.max() / ((1 << k) - 1) * 1.01 + 1e-7
    assert float(jnp.abs(back - x).max()) <= bound


# --- the k=11 cell code (11 bits in 7 three-state cells) ---------------------


@settings(max_examples=15, deadline=None)
@given(
    n_words=st.integers(1, 48),
    seed=st.integers(0, 2**31 - 1),
)
def test_cell_code_11_bits_in_7_cells_roundtrip(n_words, seed):
    """bits_to_levels/levels_to_bits at (m=3, α=7) — the paper's
    headline fractional point, b = bits_for(3, 7) = 11 — now rides the
    carry fast path end-to-end and stays lossless on data bits."""
    assert codec.bits_for(3, 7) == 11
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
    nbits = n_words * 32
    levels = codec.bits_to_levels(data, nbits, 3, 7)
    assert int(np.asarray(levels).max(initial=0)) < 3
    back = codec.levels_to_bits(levels, 3, 7)
    assert (np.asarray(back)[:n_words] == np.asarray(data)).all()


@settings(max_examples=10, deadline=None)
@given(
    m=st.sampled_from([3, 5, 6, 7]),       # fractional bits-per-cell points
    n_words=st.integers(1, 24),
    seed=st.integers(0, 2**31 - 1),
)
def test_cell_code_fractional_ladder_roundtrip(m, n_words, seed):
    """Every fractional rung of the degradation ladder (m ∉ powers of
    two at its best α) round-trips through the carry pack."""
    alpha = codec.best_alpha(m)
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
    levels = codec.bits_to_levels(data, n_words * 32, m, alpha)
    back = codec.levels_to_bits(levels, m, alpha)
    assert (np.asarray(back)[:n_words] == np.asarray(data)).all()


# --- dispatch mode validation ------------------------------------------------


def test_env_mode_unknown_raises_listing_valid_modes():
    """An unknown REPRO_FRAC_MODE must fail loudly (ValueError naming
    the valid modes), never silently fall through to a backend."""
    with _with_env_mode("mosaic_turbo"):
        with pytest.raises(ValueError) as ei:
            fops.encode_tensor(jnp.zeros((8,), jnp.float32), kbits=8)
    msg = str(ei.value)
    assert "mosaic_turbo" in msg
    for valid in fops.VALID_MODES:
        assert valid in msg


def test_explicit_mode_unknown_raises():
    with pytest.raises(ValueError) as ei:
        fops.encode_tensor(jnp.zeros((8,), jnp.float32), kbits=8,
                           mode="bogus")
    assert "bogus" in str(ei.value)


def test_explicit_pallas_out_of_range_k_raises():
    with pytest.raises(ValueError):
        fops.encode_tensor(jnp.zeros((8,), jnp.float32), kbits=20,
                           mode="pallas_interpret")


def test_env_mode_fractional_k_stays_bit_exact():
    """REPRO_FRAC_MODE=pallas_interpret really runs the kernel for a
    fractional width (no silent jnp reroute): words match the oracle
    and the probe-free interpret path is engaged."""
    x = jnp.asarray(np.random.default_rng(7).normal(size=500), jnp.float32)
    ref = codec.frac_encode_tensor(x, kbits=11)
    with _with_env_mode("pallas_interpret"):
        blob = fops.encode_tensor(x, kbits=11)
    assert (np.asarray(blob["words"]) == np.asarray(ref["words"])).all()
