"""Pallas TPU kernel: batched Keccak-f[1600] (paper §II-A, SHA3 engine).

TPU has no 64-bit integer datapath, so Keccak lanes are (lo, hi) 32-bit
pairs.  In the kernel the message batch runs along the 128-wide vector
lanes: the state is two (25, bm) int32 tiles (lo words, hi words), and
each Keccak lane is one (1, bm) row — the wrapper transposes the
(B, 25, 2) uint32 state in and out and pads B to a multiple of 128.
The 24 rounds run in a fori_loop whose round constant is read from an
SMEM table indexed by the loop counter; theta/rho/pi/chi are unrolled
over the 25 rows with static rotation counts (logical right shifts on
int32), which Mosaic turns into pure VPU bitwise traffic — the CPE
engine of the Amoeba mapping.

Oracle: ref.py (numpy uint64) which is itself validated against
hashlib.sha3_256.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sha3.ref import N_ROUNDS, PI, RC, RHO

# RC as (24, 2) int32 [lo, hi] bit patterns (the SMEM round table)
RC32 = np.stack([RC.astype(np.uint64) & np.uint64(0xFFFFFFFF),
                 RC.astype(np.uint64) >> np.uint64(32)],
                axis=1).astype(np.uint32).view(np.int32)
LANES = 128


def _rotl_pair(lo, hi, r: int):
    """64-bit rotate-left on (lo, hi) int32 pairs, static r."""
    r = r % 64
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r < 32:
        srl = jax.lax.shift_right_logical
        nlo = (lo << r) | srl(hi, jnp.int32(32 - r))
        nhi = (hi << r) | srl(lo, jnp.int32(32 - r))
        return nlo, nhi
    return _rotl_pair(hi, lo, r - 32)


def keccak_kernel(rc_ref, lo_ref, hi_ref, olo_ref, ohi_ref):
    """rc_ref: (24, 2) int32 in SMEM; lo/hi: (25, bm) int32 state words."""

    def round_fn(rnd, st):
        lo, hi = list(st[:25]), list(st[25:])
        # theta
        clo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20]
               for x in range(5)]
        chi_ = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20]
                for x in range(5)]
        for x in range(5):
            rl, rh = _rotl_pair(clo[(x + 1) % 5], chi_[(x + 1) % 5], 1)
            dlo = clo[(x - 1) % 5] ^ rl
            dhi = chi_[(x - 1) % 5] ^ rh
            for y in range(5):
                lo[x + 5 * y] = lo[x + 5 * y] ^ dlo
                hi[x + 5 * y] = hi[x + 5 * y] ^ dhi
        # rho + pi
        blo = [None] * 25
        bhi = [None] * 25
        for l in range(25):
            blo[PI[l]], bhi[PI[l]] = _rotl_pair(lo[l], hi[l], RHO[l])
        # chi
        for y in range(5):
            rl = [blo[x + 5 * y] for x in range(5)]
            rh = [bhi[x + 5 * y] for x in range(5)]
            for x in range(5):
                lo[x + 5 * y] = rl[x] ^ (~rl[(x + 1) % 5] & rl[(x + 2) % 5])
                hi[x + 5 * y] = rh[x] ^ (~rh[(x + 1) % 5] & rh[(x + 2) % 5])
        # iota
        lo[0] = lo[0] ^ rc_ref[rnd, 0]
        hi[0] = hi[0] ^ rc_ref[rnd, 1]
        return tuple(lo + hi)

    st = tuple(lo_ref[l:l + 1, :] for l in range(25)) \
        + tuple(hi_ref[l:l + 1, :] for l in range(25))
    st = jax.lax.fori_loop(0, N_ROUNDS, round_fn, st)
    for l in range(25):
        olo_ref[l:l + 1, :] = st[l]
        ohi_ref[l:l + 1, :] = st[25 + l]


@partial(jax.jit, static_argnames=("block_batch", "interpret"))
def keccak_f_pallas(state: jax.Array, block_batch: int = 4 * LANES,
                    interpret: bool = False) -> jax.Array:
    """state: (B, 25, 2) uint32 [lo, hi] -> permuted."""
    B = state.shape[0]
    words = jax.lax.bitcast_convert_type(state, jnp.int32)
    bp = -(-B // LANES) * LANES
    bm = min(block_batch, bp)
    bp = -(-bp // bm) * bm
    lo, hi = (jnp.pad(words[:, :, i].T, ((0, 0), (0, bp - B)))
              for i in (0, 1))
    tile = pl.BlockSpec((25, bm), lambda i: (0, i))
    olo, ohi = pl.pallas_call(
        keccak_kernel,
        out_shape=(jax.ShapeDtypeStruct((25, bp), jnp.int32),) * 2,
        grid=(bp // bm,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=(tile, tile),
        interpret=interpret,
    )(jnp.asarray(RC32), lo, hi)
    out = jnp.stack([olo[:, :B].T, ohi[:, :B].T], axis=-1)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
