"""SHA3 Pallas kernel vs numpy oracle vs hashlib."""
import hashlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.sha3 import ops, ref


def test_keccak_f_zero_state_vector():
    out = ref.keccak_f(np.zeros((1, 25), np.uint64))
    assert out[0, 0] == np.uint64(0xF1258F7940E1DDE7)
    assert out[0, 1] == np.uint64(0x84D5CCF933C0478A)


@settings(max_examples=20, deadline=None)
@given(st.binary(min_size=0, max_size=500))
def test_ref_matches_hashlib(msg):
    assert ref.sha3_256([msg])[0] == hashlib.sha3_256(msg).digest()


@pytest.mark.parametrize("sizes", [
    [0, 1, 135, 136, 137],
    [272, 271, 273],
    [1000],
])
def test_kernel_matches_hashlib_batched(sizes):
    msgs = [bytes([i % 256] * s) for i, s in enumerate(sizes)]
    want = [hashlib.sha3_256(m).digest() for m in msgs]
    assert ops.sha3_256(msgs, interpret=True) == want


def test_kernel_matches_ref_permutation():
    rng = np.random.default_rng(0)
    st64 = rng.integers(0, 2**63, (16, 25)).astype(np.uint64)
    want = ref.keccak_f(st64)
    import jax.numpy as jnp
    from repro.kernels.sha3.sha3 import keccak_f_pallas

    pairs = ops._to_pairs(st64)
    got = ops._to_u64(np.asarray(keccak_f_pallas(jnp.asarray(pairs),
                                                 interpret=True)))
    assert (got == want).all()


def test_hash_array_integrity_semantics():
    x = np.arange(64, dtype=np.float32)
    h1 = ops.hash_array(x, interpret=True)
    x2 = x.copy()
    x2[3] += 1e-6
    assert h1 != ops.hash_array(x2, interpret=True)
    assert h1 == hashlib.sha3_256(x.tobytes()).digest()
