"""Pallas TPU kernel: batched iterative NTT with Montgomery reduction.

Design (Amoeba MPE adaptation, DESIGN.md §2):
  - one grid cell = a (block_batch, N) tile resident in VMEM
    (8 × 4096 × 4 B = 128 KB — fits comfortably);
  - the log2(N) butterfly stages run *inside* the kernel, unrolled in
    Python so every stage has static shapes;
  - all modular arithmetic is int32 Montgomery (R = 2^16): with
    q = 12289 < 2^14, t + m·q < 2^30 never overflows;
  - twiddles arrive bit-exact in Montgomery form, so data stays in the
    standard domain end-to-end (REDC(a · bR) = a·b mod q);
  - bit-reversal is done by the ops.py wrapper (a gather is cheap there
    and lane-hostile in-kernel).

TPU layout: the transform axis stays whole on the 128-wide lane axis.
Stage ``h`` pairs element ``i`` with ``i ^ h``; rather than splitting
the lanes into ``(n/2h, 2, h)`` (which Mosaic cannot relayout for
``h < 128``), every element reads its partner through two lane rolls
(``x[i+h]``, ``x[i-h]``), picks its role from ``i & h``, and multiplies
by a per-stage, per-lane twiddle row ``tw[h + i % h]`` (ops.py builds
the ``(log2 N, N)`` table).  Both halves of a butterfly compute the
same product, so the stage costs two multiplies per pair instead of
one, all lane-dense.  Compiled kernels need ``N % 128 == 0``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R_BITS = 16
R = 1 << R_BITS


def montgomery_constants(q: int) -> tuple[int, int, int]:
    """(q' = -q^-1 mod R, R mod q, R^2 mod q)."""
    q_inv = pow(q, -1, R)
    return (R - q_inv) % R, R % q, (R * R) % q


def _redc(t: jnp.ndarray, q: int, q_prime: int) -> jnp.ndarray:
    """Montgomery REDC: t < q·R  ->  t·R^-1 mod q, result in [0, q)."""
    m = (t * q_prime) & (R - 1)
    u = (t + m * q) >> R_BITS
    return jnp.where(u >= q, u - q, u)


def _mulredc(a: jnp.ndarray, b_mont: jnp.ndarray, q: int, q_prime: int):
    """a (standard) × b (Montgomery) -> a·b mod q (standard)."""
    return _redc(a * b_mont, q, q_prime)


def _addmod(a, b, q):
    s = a + b
    return jnp.where(s >= q, s - q, s)


def _submod(a, b, q):
    d = a - b
    return jnp.where(d < 0, d + q, d)


def ntt_kernel(x_ref, tw_ref, o_ref, *, n: int, q: int, q_prime: int,
               n_inv_mont: int):
    """x_ref: (bm, N) int32 bit-reversed standard-domain residues.
    tw_ref: (log2 N, N) int32 Montgomery-form per-lane stage twiddles.
    n_inv_mont: N^-1·R mod q for the inverse transform, or 0 (forward).
    """
    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    h, stage = 1, 0
    while h < n:
        lo_side = (lane & h) == 0
        a = jnp.where(lo_side, x, pltpu.roll(x, h, 1))         # x[i - h]
        b = jnp.where(lo_side, pltpu.roll(x, n - h, 1), x)     # x[i + h]
        t = _mulredc(b, tw_ref[stage:stage + 1, :], q, q_prime)
        x = jnp.where(lo_side, _addmod(a, t, q), _submod(a, t, q))
        h, stage = 2 * h, stage + 1
    if n_inv_mont:
        x = _mulredc(x, jnp.int32(n_inv_mont), q, q_prime)
    o_ref[...] = x


def ntt_pallas(x_bitrev: jax.Array, tw_lanes: jax.Array, *, q: int,
               inverse: bool, block_batch: int = 8,
               interpret: bool = False) -> jax.Array:
    """x_bitrev: (B, N) int32; tw_lanes: (log2 N, N) int32.  Returns
    the transform, natural order."""
    B, n = x_bitrev.shape
    if not interpret and n % 128:
        raise ValueError(f"compiled NTT needs N % 128 == 0, got N={n}")
    q_prime, r_mod_q, _ = montgomery_constants(q)
    n_inv_mont = (pow(n, q - 2, q) * R) % q if inverse else 0
    bm = min(block_batch, B)
    assert B % bm == 0, (B, bm)
    kern = partial(ntt_kernel, n=n, q=q, q_prime=q_prime,
                   n_inv_mont=n_inv_mont)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec(tw_lanes.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        interpret=interpret,
    )(x_bitrev, tw_lanes)
