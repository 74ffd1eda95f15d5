"""Fused FRAC quantize→pack Pallas pipeline (paper §II-B hot path).

The seed implementation ran FRAC encode as three separate jnp passes —
``quantize_blocks`` → ``pack_bits`` → scatter-add into words — each of
which round-trips the full fp32 tensor through HBM, and the scatter
serializes badly.  This module fuses the whole encode into ONE kernel
pass per VMEM tile:

    per 256-element block:  absmax scale → k-bit codes → uint32 words

and the inverse (unpack → dequantize) for decode.  Bytes leave the chip
already packed, so HBM write traffic drops k/32-fold — the roofline win
the checkpoint / grad-compress / KV-cache paths are built around
(GreenFPGA's reconfigurable-primitive argument; Chasing Carbon's
"don't let overhead eat the operational savings").

Layout: a segment is one LCM(k, 32)-bit period of the packed stream:
``c_seg = 32/gcd(k,32)`` codes in exactly ``w_seg = k/gcd(k,32)``
words, word-aligned and self-contained (see ``frac_carry_pack.py``).
A 256-element block is ``S = 256/c_seg`` segments.  The wrapper
transposes the flat tensor to ``(256, n_blocks)`` — blocks on the
128-wide lane axis, row ``s·c_seg + j`` holding code ``j`` of segment
``s`` — so every in-kernel step is lane-dense: the block absmax is a
sublane reduction, code ``j`` of every segment is the stride-``c_seg``
row slice from row ``j``, and word ``w`` of every segment is a static
shift-OR of those slices written to the stride-``w_seg`` rows from
``w`` of a ``(8k, n_blocks)`` output that the wrapper transposes back.
The transposes keep the flat stream's own row order, so the wrapper's
intermediates stay the size of the tensor.  Code ``[b, s, j]`` is flat element
``b·256 + s·c_seg + j`` and lands in word ``b·8k + s·w_seg + (j·k)//32``
at offset ``(j·k) % 32`` — exactly ``codec.pack_bits`` order, so the
emitted words are bit-identical to the ``core/frac/codec.py`` oracle.
For fractional k (the 11-bits-in-7-cells cell codes) the per-segment
carry table from ``codec.seg_layout`` splits straddling codes into a
lo shift into their start word plus a hi spill into the next, both
OR-ed in statically.

Supported k: every width 1–16 (fractional widths included — this is
what puts the whole ``bits_for(m, α)`` degradation ladder on the fused
path).  See ops.encode_tensor for the dispatch.

Stochastic rounding: the caller passes the *same* uniforms the oracle
would draw (``jax.random.uniform(rng, (n_blocks, 256))``), keeping the
fused path bit-exact under rng as well.  On-TPU this could move to
``pltpu.prng_random_bits`` at the cost of oracle equality.

Measured on the CI host (CPU, jnp fallback engaged by the ops
dispatch, 1M-element fp32): fused encode ~60x over the seed
scatter-based two-pass encode at k=8 (~70x at k=4, ~50x at the
fractional k=11); fused decode ~3.5–4.3x over the seed gather path for
aligned k (the two-stage unpack→dequantize in ops.py keeps the heavy
pass fused) and ~1.1–1.8x at fractional k, where decode is bound by
the per-code column takes — the remaining fractional-decode win is
TPU-side kernel fusion.  See ``benchmarks/bench_frac.py``
codec-throughput rows for live numbers (BENCH_frac.json via
``run.py --json``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frac.codec import BLOCK, seg_geometry, seg_layout

TILE_BLOCKS = 128         # 256-element blocks per grid cell (lanes);
                          # fewer blocks make one full-width tile

SUPPORTED_K = tuple(range(1, 17))


def words_per_block(k: int) -> int:
    """uint32 words one 256-element block packs into (256·k/32 = 8k)."""
    return BLOCK * k // 32


def block_layout(k: int) -> tuple[int, int, int]:
    """(segments per block, codes per segment, words per segment).

    A 256-element block is always a whole number of segments (c_seg is
    a power of two ≤ 32), and S·w_seg == words_per_block(k)."""
    c_seg, w_seg = seg_geometry(k)
    return BLOCK // c_seg, c_seg, w_seg


# ---------------------------------------------------------------------------
# correctly rounded division
#
# The codec quantizes ``x / scale`` with IEEE division, but f32
# division on a TPU v5e is not correctly rounded: on 16M normal values
# a third of the quotients came out one ulp off and 0.3% two ulps off,
# flipping about one code in a million at a rounding boundary (the jnp
# codec under XLA on the chip is off the same way).  ``div_rn``
# repairs a quotient with exact float arithmetic only (IEEE
# multiply/add, no FMA): Dekker's product gives ``x - r·s`` exactly,
# and Shewchuk's expansion sum decides its sign against each half-ulp
# midpoint.
# ---------------------------------------------------------------------------


def _two_sum(a, b):
    """a + b == s + e exactly."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _split(a):
    """Veltkamp split: a == hi + lo, each with at most 12 significant
    bits, so products of halves are exact."""
    t = 4097.0 * a
    hi = t - (t - a)
    return hi, a - hi


def _sign3(a, b, c):
    """Sign of a + b + c (b's magnitude below a's ulp, as _two_sum
    leaves it), exact: the nonoverlapping expansion of the sum has the
    sign of its largest nonzero component."""
    q, h0 = _two_sum(c, b)
    q, h1 = _two_sum(q, a)
    top = jnp.where(q != 0, q, jnp.where(h1 != 0, h1, h0))
    return jnp.where(top > 0, 1, jnp.where(top < 0, -1, 0))


def div_rn(x, s, r):
    """IEEE round-to-nearest-even ``x / s`` for ``s > 0``, given ``r``
    within one ulp of it; from farther away, ``r`` moves one ulp toward
    it.  Exact while ``s < 2^100`` (no overflow in the split) and away
    from the subnormal range; there the quotient is either tiny against
    the codec's ``+ 1`` or ``r`` is kept."""
    ar = jnp.abs(r)
    bits = jax.lax.bitcast_convert_type(ar, jnp.int32)
    up = jax.lax.bitcast_convert_type(bits + 1, jnp.float32)
    dn = jax.lax.bitcast_convert_type(jnp.maximum(bits - 1, 0), jnp.float32)
    p = ar * s
    rh, rl = _split(ar)
    sh, sl = _split(s)
    pe = ((rh * sh - p) + rh * sl + rl * sh) + rl * sl  # ar·s == p + pe
    a, b = _two_sum(jnp.abs(x) - p, -pe)                # |x| - ar·s == a + b
    # the true quotient lies above the midpoint to ``up`` (or on it,
    # with ``up`` even), or below the midpoint to ``dn``
    above = _sign3(a, b, -s * (0.5 * (up - ar)))
    below = _sign3(a, b, s * (0.5 * (ar - dn)))
    odd = (bits & 1) == 1                   # ties go to the even neighbour
    go_up = (above > 0) | ((above == 0) & odd)
    go_dn = (ar > 0) & ((below < 0) | ((below == 0) & odd))
    fixed = jnp.where(go_up, up, jnp.where(go_dn, dn, ar))
    fixed = jnp.where(s < 2.0 ** 100, fixed, ar)
    return jnp.where(x < 0, -fixed, fixed)


# ---------------------------------------------------------------------------
# kernels (tiles are (rows, TILE_BLOCKS): one 256-element block per lane)
# ---------------------------------------------------------------------------


def _encode_kernel(*refs, k: int, stochastic: bool, interpret: bool):
    """One pass: absmax scale → quantize → carry-table shift-OR pack.

    x tile (and the stochastic uniforms): (256, TB) fp32, row
    s·c_seg + j = code j of segment s; words out: (8k, TB) uint32, row
    s·w_seg + w = word w of segment s; scales out: (1, TB) fp32.  The
    whole tile quantizes at once into the codes scratch, from which
    code j of every segment is the stride-c_seg row slice starting at
    row j (word w is written the same way).  The static ``seg_layout``
    table splits boundary-straddling codes into lo/hi contributions
    (w_seg == 1 for aligned k: no straddlers)."""
    if stochastic:
        x_ref, u_ref, o_words_ref, o_scales_ref, codes_ref = refs
    else:
        x_ref, o_words_ref, o_scales_ref, codes_ref = refs
    q = (1 << k) - 1
    S, c_seg, w_seg = block_layout(k)
    _, _, _, contrib = seg_layout(k)
    x = x_ref[...]
    scale = jnp.max(jnp.abs(x), axis=0, keepdims=True) + 1e-12
    r = x / scale
    if not interpret:                   # the interpreter divides exactly
        # two steps: the chip's quotient can be two ulps off
        r = div_rn(x, scale, div_rn(x, scale, r))
    t = (r + 1.0) * (0.5 * q)
    if stochastic:
        # stochastic rounding, same FMA-immune form as
        # codec.quantize_blocks: floor(t) + (frac(t) + u >= 1).  The
        # interpreter runs on XLA, which would contract t's multiply
        # into the subtraction below; Mosaic has no such barrier (and no
        # fp32 FMA on the VPU)
        if interpret:
            t = jax.lax.optimization_barrier(t)
        tf = jnp.floor(t)
        t = tf + ((t - tf) + u_ref[...] >= 1.0).astype(jnp.float32)
    else:
        t = jnp.round(t)
    # via int32: codes < 2^16 fit, and Mosaic has no direct float32 <->
    # uint32 conversion
    codes_ref[...] = jnp.clip(t, 0, q).astype(jnp.int32).astype(jnp.uint32)
    for w in range(w_seg):                   # disjoint bit ranges: or-accumulate
        acc = None
        for j, sh, is_hi in contrib[w]:
            c = codes_ref[pl.ds(j, S, stride=c_seg), :]
            term = (c >> jnp.uint32(sh)) if is_hi else (c << jnp.uint32(sh))
            acc = term if acc is None else acc | term
        o_words_ref[pl.ds(w, S, stride=w_seg), :] = acc
    o_scales_ref[...] = scale


def _decode_kernel(words_ref, scales_ref, o_ref, *, k: int):
    """Inverse pass: static carry unpack → dequantize against block
    scale.  Straddling codes OR their start word's high bits with the
    next word's low bits (the inverse carry)."""
    q = (1 << k) - 1
    S, c_seg, w_seg = block_layout(k)
    w0, shift, spill, _ = seg_layout(k)
    mask = jnp.uint32(q)
    # same fusion-immune form as codec.dequantize_blocks (bit-exact):
    # exact integer 2c - q, constant fp32 reciprocal, plain multiplies
    inv_q = float(np.float32(1.0) / np.float32(q))
    sc = scales_ref[...] * inv_q             # (1, TB)
    for j in range(c_seg):
        v = words_ref[pl.ds(w0[j], S, stride=w_seg), :] >> jnp.uint32(shift[j])
        if spill[j]:
            hi = words_ref[pl.ds(w0[j] + 1, S, stride=w_seg), :]
            v = v | (hi << jnp.uint32(32 - shift[j]))
        c = (v & mask).astype(jnp.int32).astype(jnp.float32)
        o_ref[pl.ds(j, S, stride=c_seg), :] = (c * 2.0 - q) * sc


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _to_lanes(a: jax.Array, width: int, gb: int) -> jax.Array:
    """Flat block-major stream -> (width, gb): one block per lane, the
    block axis zero-padded out to the grid."""
    nb = a.size // width
    a = a.reshape(nb, width).T
    return jnp.pad(a, ((0, 0), (0, gb - nb))) if gb > nb else a


def _from_lanes(a: jax.Array, nb: int) -> jax.Array:
    """Inverse of ``_to_lanes`` -> flat, block-major, unpadded."""
    return a[:, :nb].T.reshape(-1)


def _grid(nb: int) -> tuple[int, int]:
    """(blocks per tile, tiles): a tensor of fewer than TILE_BLOCKS
    blocks is one tile as wide as itself (a block equal to the array's
    extent meets the lane tiling rule), so small tensors are not padded
    out to 128 blocks."""
    tb = max(1, min(nb, TILE_BLOCKS))
    return tb, pl.cdiv(nb, tb)


def _tiles(rows: int, tb: int):
    return pl.BlockSpec((rows, tb), lambda i: (0, i))


@partial(jax.jit, static_argnames=("k", "stochastic", "interpret"))
def _quant_pack_call(flat, u, k: int, stochastic: bool, interpret: bool):
    nb = flat.shape[0] // BLOCK
    tb, grid = _grid(nb)
    gb = grid * tb
    wpb = words_per_block(k)
    args = [_to_lanes(flat, BLOCK, gb)]
    if stochastic:
        args.append(_to_lanes(u, BLOCK, gb))
    words, scales = pl.pallas_call(
        partial(_encode_kernel, k=k, stochastic=stochastic,
                interpret=interpret),
        out_shape=(
            jax.ShapeDtypeStruct((wpb, gb), jnp.uint32),
            jax.ShapeDtypeStruct((1, gb), jnp.float32),
        ),
        grid=(grid,),
        in_specs=[_tiles(BLOCK, tb)] * len(args),
        out_specs=(_tiles(wpb, tb), _tiles(1, tb)),
        scratch_shapes=[pltpu.VMEM((BLOCK, tb), jnp.uint32)],
        interpret=interpret,
    )(*args)
    return _from_lanes(words, nb), scales[0, :nb]


def quant_pack(flat: jax.Array, k: int, *, rng: jax.Array | None = None,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """flat (N,) float -> (words (⌈N/256⌉·8k,) uint32, scales (⌈N/256⌉,)).

    Bit-identical to ``codec.quantize_blocks`` + ``codec.pack_bits``."""
    assert k in SUPPORTED_K, f"fused path needs 1 <= k <= 16, got {k}"
    flat = flat.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    nb = -(-n // BLOCK)
    pad = nb * BLOCK - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if rng is not None:
        # identical draw to the oracle: uniform(rng, (nb, BLOCK))
        u = jax.random.uniform(rng, (nb, BLOCK)).reshape(-1)
    else:
        u = jnp.zeros((0,), jnp.float32)         # unused placeholder
    return _quant_pack_call(flat, u, k, rng is not None, interpret)


@partial(jax.jit, static_argnames=("k", "interpret"))
def _unpack_dequant_call(words, scales, k: int, interpret: bool):
    nb = scales.shape[0]
    tb, grid = _grid(nb)
    gb = grid * tb
    wpb = words_per_block(k)
    sc = jnp.pad(scales, (0, gb - nb)).reshape(1, gb)
    x = pl.pallas_call(
        partial(_decode_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((BLOCK, gb), jnp.float32),
        grid=(grid,),
        in_specs=[_tiles(wpb, tb), _tiles(1, tb)],
        out_specs=_tiles(BLOCK, tb),
        interpret=interpret,
    )(_to_lanes(words, wpb, gb), sc)
    return _from_lanes(x, nb)


def unpack_dequant(words: jax.Array, scales: jax.Array, k: int, n: int, *,
                   interpret: bool = False) -> jax.Array:
    """Inverse of quant_pack -> (n,) fp32.  Matches
    ``codec.unpack_bits`` + ``codec.dequantize_blocks``."""
    assert k in SUPPORTED_K, f"fused path needs 1 <= k <= 16, got {k}"
    nb = scales.shape[0]
    assert words.shape[0] == nb * words_per_block(k), \
        (words.shape, nb, words_per_block(k))
    return _unpack_dequant_call(words, scales, k, interpret)[:n]
