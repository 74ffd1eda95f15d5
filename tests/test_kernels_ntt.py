"""NTT Pallas kernel vs pure-jnp oracle vs schoolbook (paper §II-A)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ntt import ops, ref


@pytest.mark.parametrize("n", [128, 256, 1024, 4096])
@pytest.mark.parametrize("batch", [1, 8])
def test_kernel_matches_ref(n, batch):
    rng = np.random.default_rng(n + batch)
    x = jnp.asarray(rng.integers(0, ref.Q, (batch, n)), jnp.int32)
    assert (np.asarray(ops.ntt(x, interpret=True)) == np.asarray(ref.ntt(x))).all()


@pytest.mark.parametrize("n", [128, 1024, 4096])
def test_intt_inverts_ntt(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(0, ref.Q, (4, n)), jnp.int32)
    back = ops.intt(ops.ntt(x, interpret=True), interpret=True)
    assert (np.asarray(back) == np.asarray(x)).all()


@settings(max_examples=10, deadline=None)
@given(
    logn=st.integers(5, 9),
    seed=st.integers(0, 2**31 - 1),
)
def test_negacyclic_vs_schoolbook(logn, seed):
    n = 1 << logn
    rng = np.random.default_rng(seed)
    a = rng.integers(0, ref.Q, n).astype(np.int32)
    b = rng.integers(0, ref.Q, n).astype(np.int32)
    got = np.asarray(ops.negacyclic_mul(jnp.asarray(a), jnp.asarray(b),
                                         interpret=True))
    want = ref.schoolbook_negacyclic(a, b)
    assert (got == want).all()


def test_convolution_theorem_cyclic():
    """NTT(a)·NTT(b) -> INTT == cyclic convolution."""
    n = 512
    rng = np.random.default_rng(0)
    a = rng.integers(0, ref.Q, n).astype(np.int64)
    b = rng.integers(0, ref.Q, n).astype(np.int64)
    fa = ops.ntt(jnp.asarray(a, jnp.int32), interpret=True).astype(jnp.int32)
    fb = ops.ntt(jnp.asarray(b, jnp.int32), interpret=True).astype(jnp.int32)
    prod = (np.asarray(fa).astype(np.int64) * np.asarray(fb)) % ref.Q
    got = np.asarray(ops.intt(jnp.asarray(prod, jnp.int32), interpret=True))
    # numpy cyclic convolution oracle
    full = np.zeros(2 * n, np.int64)
    for i in range(n):
        full[i: i + n] += a[i] * b
    want = ((full[:n] + full[n:]) % ref.Q).astype(np.int32)
    assert (got == want).all()


def test_montgomery_constants():
    from repro.kernels.ntt.ntt import R, montgomery_constants

    q = ref.Q
    q_prime, r_mod_q, r2 = montgomery_constants(q)
    assert (q * ((R - q_prime) % R)) % R == 1     # q' = -q^-1 mod R
    assert r_mod_q == R % q and r2 == (R * R) % q


def test_dtypes_stay_int32():
    x = jnp.asarray(np.arange(256) % ref.Q, jnp.int32).reshape(1, 256)
    assert ops.ntt(x, interpret=True).dtype == jnp.int32


def test_32k_batch_shape():
    x = jnp.asarray(np.random.default_rng(0).integers(0, ref.Q, 32768), jnp.int32)
    y = ops.ntt_32k(x, interpret=True)
    assert y.shape == x.shape
    # each 4096 row independently invertible
    back = ops.intt(y.reshape(8, 4096), interpret=True)
    assert (np.asarray(back).reshape(-1) == np.asarray(x)).all()


def test_impossible_modulus_raises():
    with pytest.raises(AssertionError):
        ref.primitive_root(32768, ref.Q)   # 32768 does not divide q-1
