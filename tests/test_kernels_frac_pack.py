"""FRAC pack/unpack Pallas kernels vs the jnp codec oracle.

Covers the seed pack32/unpack32 word kernels, the fractional-width
carry kernels (frac_carry_pack) and the fused quantize→pack pipeline
(frac_quant_pack + the ops dispatch): words, scales AND decoded floats
must be bit-identical to core/frac/codec.py across every width 1..16
(including the fractional cell-code widths 3/5/7/11/13), odd lengths
(block padding), every dispatch mode, and stochastic-rounding rng
on/off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.frac import codec
from repro.kernels.frac_pack import frac_carry_pack, frac_quant_pack, \
    ops as fops
from repro.kernels.frac_pack.frac_pack import pack32, unpack32

MODES = ("jnp", "pallas_interpret")
FRACTIONAL_K = (3, 5, 7, 11, 13)


@pytest.mark.parametrize("k", [2, 4, 8, 16])
@pytest.mark.parametrize("n_words", [64, 1024, 4096])
def test_pack32_matches_codec(k, n_words):
    n = n_words * (32 // k)
    rng = np.random.default_rng(k * n_words)
    codes = jnp.asarray(rng.integers(0, 1 << k, n), jnp.uint32)
    got = pack32(codes, k, interpret=True)
    want = codec.pack_bits(codes, k)
    assert (np.asarray(got) == np.asarray(want)).all()
    back = unpack32(got, k, n, interpret=True)
    assert (np.asarray(back) == np.asarray(codes)).all()


@settings(max_examples=15, deadline=None)
@given(
    k=st.sampled_from([4, 8]),
    rows=st.integers(1, 40),
    cols=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_tensor_path_matches_codec(k, rows, cols, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    blob_k = fops.encode_tensor(x, kbits=k)
    blob_r = codec.frac_encode_tensor(x, kbits=k)
    assert (np.asarray(blob_k["words"]) == np.asarray(blob_r["words"])).all()
    xk = np.asarray(fops.decode_tensor(blob_k))
    xr = np.asarray(codec.frac_decode_tensor(blob_r))
    assert (xk == xr).all()


def test_dtype_sweep():
    for dt in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)), dt)
        blob = fops.encode_tensor(x, kbits=8)
        back = fops.decode_tensor(blob)
        assert back.dtype == dt and back.shape == x.shape


# --- fused quantize→pack pipeline ------------------------------------------------


@pytest.mark.parametrize("k", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [255, 256, 257, 1000, 4096])
def test_fused_pipeline_bit_exact_all_k(k, n):
    """Fused encode/decode == oracle, bit-for-bit: words, scales AND
    decoded floats, for every supported k, padded and exact lengths."""
    rng = np.random.default_rng(k * 1000 + n)
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    ref = codec.frac_encode_tensor(x, kbits=k)
    ref_dec = np.asarray(codec.frac_decode_tensor(ref))
    for mode in MODES:
        blob = fops.encode_tensor(x, kbits=k, mode=mode)
        assert (np.asarray(blob["words"]) == np.asarray(ref["words"])).all(), mode
        assert (np.asarray(blob["scales"]) == np.asarray(ref["scales"])).all(), mode
        dec = np.asarray(fops.decode_tensor(blob, mode=mode))
        assert (dec == ref_dec).all(), mode


@settings(max_examples=10, deadline=None)
@given(
    k=st.sampled_from([2, 4, 8, 16]),
    n=st.integers(1, 2000),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_pipeline_property_roundtrip(k, n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=n) * rng.uniform(0.01, 100), jnp.float32)
    ref = codec.frac_encode_tensor(x, kbits=k)
    ref_dec = np.asarray(codec.frac_decode_tensor(ref))
    blob = fops.encode_tensor(x, kbits=k, mode="jnp")
    assert (np.asarray(blob["words"]) == np.asarray(ref["words"])).all()
    assert (np.asarray(fops.decode_tensor(blob)) == ref_dec).all()
    # quantization error bound survives the fused path
    scales = np.asarray(blob["scales"])
    bound = scales.max() / ((1 << k) - 1) * 1.01 + 1e-7
    assert np.abs(ref_dec - np.asarray(x)).max() <= bound


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("k", [2, 4, 8, 16])
def test_fused_pipeline_stochastic_rounding_matches_oracle(k, mode):
    """Same rng key -> identical words with stochastic rounding on."""
    rng_np = np.random.default_rng(k)
    x = jnp.asarray(rng_np.normal(size=1000), jnp.float32)
    key = jax.random.PRNGKey(k)
    ref = codec.frac_encode_tensor(x, kbits=k, rng=key)
    blob = fops.encode_tensor(x, kbits=k, rng=key, mode=mode)
    assert (np.asarray(blob["words"]) == np.asarray(ref["words"])).all()
    # and rng on/off genuinely differ (stochastic vs nearest)
    det = fops.encode_tensor(x, kbits=k, mode=mode)
    assert not (np.asarray(det["words"]) == np.asarray(blob["words"])).all()


def test_fused_kernel_direct_quant_pack_roundtrip():
    """frac_quant_pack.quant_pack/unpack_dequant without the dispatch."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=3000), jnp.float32)
    for k in frac_quant_pack.SUPPORTED_K:
        words, scales = frac_quant_pack.quant_pack(x, k, interpret=True)
        codes_ref, scales_ref = codec.quantize_blocks(x, k)
        assert (np.asarray(words)
                == np.asarray(codec.pack_bits(codes_ref, k))).all()
        assert (np.asarray(scales) == np.asarray(scales_ref)).all()
        back = frac_quant_pack.unpack_dequant(words, scales, k, x.shape[0],
                                              interpret=True)
        ref = codec.dequantize_blocks(codes_ref, scales_ref, k, x.shape[0])
        assert (np.asarray(back) == np.asarray(ref)).all()


def test_fake_quant_matches_encode_decode():
    x = jnp.asarray(np.random.default_rng(5).normal(size=2000), jnp.float32)
    for k in (2, 4, 8):
        fq = fops.fake_quant(x, k)
        ed = fops.decode_tensor(fops.encode_tensor(x, kbits=k))
        assert (np.asarray(fq) == np.asarray(ed)).all()


def test_dispatch_resolves_fractional_k_first_class():
    """Fractional widths are first-class in the dispatch: every width
    1..16 resolves to a real backend (auto mode), explicit kernel modes
    are accepted for them, and out-of-range widths only work via jnp."""
    for k in range(1, 17):
        assert fops.default_mode(k) in fops.VALID_MODES
        assert fops._resolve_mode(k, "pallas_interpret") == "pallas_interpret"
    # k > 16: no kernel — auto resolves to jnp, explicit pallas raises
    assert fops._resolve_mode(23, None) == "jnp"
    with pytest.raises(ValueError):
        fops._resolve_mode(23, "pallas_interpret")


# --- fractional widths: cross-word-carry kernels ---------------------------------


@pytest.mark.parametrize("k", FRACTIONAL_K)
@pytest.mark.parametrize("n", [255, 256, 257, 1000])
def test_fractional_fused_pipeline_bit_exact(k, n):
    """Fused quantize→pack and unpack→dequantize at fractional widths:
    words, scales AND decoded floats bit-identical to the codec oracle,
    through the interpret-mode kernel and the jnp dispatch."""
    rng = np.random.default_rng(k * 1000 + n)
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    ref = codec.frac_encode_tensor(x, kbits=k)
    ref_dec = np.asarray(codec.frac_decode_tensor(ref))
    for mode in MODES:
        blob = fops.encode_tensor(x, kbits=k, mode=mode)
        assert (np.asarray(blob["words"])
                == np.asarray(ref["words"])).all(), (k, mode)
        assert (np.asarray(blob["scales"])
                == np.asarray(ref["scales"])).all(), (k, mode)
        dec = np.asarray(fops.decode_tensor(blob, mode=mode))
        assert (dec == ref_dec).all(), (k, mode)


@pytest.mark.parametrize("k", FRACTIONAL_K)
def test_fractional_kernel_direct_words_scales_decode(k):
    """frac_quant_pack without the dispatch, fractional k: the kernel's
    carry table must reproduce the codec words exactly."""
    x = jnp.asarray(np.random.default_rng(k).normal(size=3000), jnp.float32)
    words, scales = frac_quant_pack.quant_pack(x, k, interpret=True)
    codes_ref, scales_ref = codec.quantize_blocks(x, k)
    assert (np.asarray(words)
            == np.asarray(codec.pack_bits(codes_ref, k))).all()
    assert (np.asarray(scales) == np.asarray(scales_ref)).all()
    back = frac_quant_pack.unpack_dequant(words, scales, k, x.shape[0],
                                          interpret=True)
    ref = codec.dequantize_blocks(codes_ref, scales_ref, k, x.shape[0])
    assert (np.asarray(back) == np.asarray(ref)).all()


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("k", FRACTIONAL_K)
def test_fractional_stochastic_rounding_matches_oracle(k, mode):
    """Stochastic-rounding bump parity at fractional widths: the same
    rng key produces identical words, and rng on/off genuinely differ."""
    x = jnp.asarray(np.random.default_rng(k + 77).normal(size=1000),
                    jnp.float32)
    key = jax.random.PRNGKey(k)
    ref = codec.frac_encode_tensor(x, kbits=k, rng=key)
    blob = fops.encode_tensor(x, kbits=k, rng=key, mode=mode)
    assert (np.asarray(blob["words"]) == np.asarray(ref["words"])).all()
    det = fops.encode_tensor(x, kbits=k, mode=mode)
    assert not (np.asarray(det["words"]) == np.asarray(blob["words"])).all()


@settings(max_examples=20, deadline=None)
@given(
    k=st.integers(1, 16),
    n=st.integers(1, 1200),
    seed=st.integers(0, 2**31 - 1),
)
def test_carry_kernel_pair_property(k, n, seed):
    """pack_carry/unpack_carry (the fractional-width Pallas pair) vs
    codec.pack_bits AND the seed scatter oracle, any width 1..16."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(
        rng.integers(0, 1 << k, n, dtype=np.int64).astype(np.uint32))
    got = frac_carry_pack.pack_carry(vals, k, interpret=True)
    want = codec.pack_bits_scatter(vals, k)
    assert got.shape == want.shape
    assert (np.asarray(got) == np.asarray(want)).all()
    back = frac_carry_pack.unpack_carry(got, k, n, interpret=True)
    assert (np.asarray(back) == np.asarray(vals)).all()


def test_fused_pipeline_all_widths_1_to_16():
    """Every width the degradation ladder can emit takes the fused
    path and round-trips bit-exactly (jnp dispatch)."""
    x = jnp.asarray(np.random.default_rng(42).normal(size=777), jnp.float32)
    for k in range(1, 17):
        ref = codec.frac_encode_tensor(x, kbits=k)
        blob = fops.encode_tensor(x, kbits=k, mode="jnp")
        assert (np.asarray(blob["words"])
                == np.asarray(ref["words"])).all(), k
        assert (np.asarray(fops.decode_tensor(blob, mode="jnp"))
                == np.asarray(codec.frac_decode_tensor(ref))).all(), k


def test_compressed_nbytes_single_source_of_truth():
    """ops.compressed_nbytes predicts the real encoded size without
    building a blob — the serving engine's KV-cache byte accounting
    must agree with an actual encode, including at fractional k=11."""
    rng = np.random.default_rng(3)
    for k in (8, 11):
        for n in (1, 255, 256, 257, 1000, 4096):
            x = jnp.asarray(rng.normal(size=n), jnp.float32)
            blob = codec.frac_encode_tensor(x, kbits=k)
            assert fops.compressed_nbytes(n, k) \
                == fops.compressed_bytes(blob), (k, n)


@pytest.mark.parametrize("off", [-2, -1, 0, 1, 2])
def test_div_rn_recovers_ieee_quotient(off):
    """The compiled encode kernel repairs the TPU's inexact f32 division
    with two ``div_rn`` steps; given a quotient up to two ulps off
    either way (or exact) they return the IEEE quotient the codec
    divides to.  Run op by op:
    a fused CPU program could contract its products into FMAs, which
    the v5e VPU, where the repair runs, does not have."""
    rng = np.random.default_rng(7)
    n = 1 << 16
    x = (rng.standard_normal(n) * 3).astype(np.float32)
    s = np.maximum(np.abs(rng.standard_normal(n)).astype(np.float32) * 3,
                   np.abs(x)) + np.float32(1e-12)
    ties = np.float32([1.0, -3.0, 0.5, 2.5, 0.0])    # x == s, exact halves
    x = np.concatenate([x, ties])
    s = np.concatenate([s, np.float32([3.0, 3.0, 1.0, 2.5, 1.0])])
    want = x / s
    r = want
    for _ in range(abs(off)):
        r = np.nextafter(r, np.float32(off * np.inf))
    r = np.where(want == 0, want, r)        # subnormal quotients: out of range
    x, s = jnp.asarray(x), jnp.asarray(s)
    got = frac_quant_pack.div_rn(x, s, jnp.asarray(r))
    if abs(off) == 2:
        assert (np.asarray(got) != want).any()     # one step: one ulp
    got = np.asarray(frac_quant_pack.div_rn(x, s, got))
    assert (got == want).all()
