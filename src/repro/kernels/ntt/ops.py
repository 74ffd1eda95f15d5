"""jit'd public API over the Pallas NTT kernel.

Every entry point compiles the kernel (TPU) unless the caller passes
``interpret=True`` (the Pallas interpreter: CPU tests and hosts).

``ntt`` / ``intt`` / ``negacyclic_mul`` match ref.py bit-for-bit
(property-tested); ``poly_mul_32k`` is the paper's 32k benchmark shape —
a 32k-point batch of q=12289 transforms (see ref.py for why a single
32k transform cannot exist at this modulus).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ntt import ref
from repro.kernels.ntt.ntt import R, montgomery_constants, ntt_pallas


@lru_cache(maxsize=None)
def _tw_lanes(n: int, q: int, inverse: bool) -> np.ndarray:
    """(log2 N, N) Montgomery-form twiddles, row s = stage h = 2^s laid
    out per lane: ``tw[h + i % h]`` (the kernel's lane-dense stages)."""
    tw = (ref.stage_twiddles(n, q, inverse).astype(np.int64) * R) % q
    lane = np.arange(n)
    rows = [tw[(1 << s) + lane % (1 << s)] for s in range(n.bit_length() - 1)]
    return np.stack(rows).astype(np.int32)


@partial(jax.jit, static_argnames=("q", "inverse", "interpret"))
def ntt(x: jax.Array, q: int = ref.Q, inverse: bool = False,
        interpret: bool = False) -> jax.Array:
    """x: (..., N) int32 in [0, q) -> cyclic NTT along the last axis."""
    shape = x.shape
    n = shape[-1]
    xb = x.reshape(-1, n)
    perm = jnp.asarray(ref.bitrev_perm(n), jnp.int32)
    tw = jnp.asarray(_tw_lanes(n, q, inverse))
    out = ntt_pallas(xb[:, perm], tw, q=q, inverse=inverse,
                     interpret=interpret)
    return out.reshape(shape)


def intt(x: jax.Array, q: int = ref.Q, interpret: bool = False) -> jax.Array:
    return ntt(x, q, inverse=True, interpret=interpret)


@partial(jax.jit, static_argnames=("q", "interpret"))
def negacyclic_mul(a: jax.Array, b: jax.Array, q: int = ref.Q,
                   interpret: bool = False) -> jax.Array:
    """(a·b) mod (x^N + 1, q) — the lattice-crypto primitive."""
    n = a.shape[-1]
    psi = jnp.asarray(ref.psi_powers(n, q), jnp.int32)
    psi_inv = jnp.asarray(ref.psi_powers(n, q, inverse=True), jnp.int32)
    at = ((a.astype(jnp.int32) * psi) % q).astype(jnp.int32)
    bt = ((b.astype(jnp.int32) * psi) % q).astype(jnp.int32)
    fa = ntt(at, q, interpret=interpret).astype(jnp.int32)
    fb = ntt(bt, q, interpret=interpret).astype(jnp.int32)
    prod = ((fa * fb) % q).astype(jnp.int32)
    out = intt(prod, q, interpret=interpret).astype(jnp.int32)
    return ((out * psi_inv) % q).astype(jnp.int32)


def ntt_32k(x: jax.Array, q: int = ref.Q, interpret: bool = False) -> jax.Array:
    """The paper's 32k-NTT benchmark shape: 32768 points at q = 12289,
    processed as a (8, 4096) batch (the largest transform the modulus
    admits — ref.py)."""
    assert x.size % 32768 == 0
    xb = x.reshape(-1, 8, 4096)
    return ntt(xb, q, interpret=interpret).reshape(x.shape)
