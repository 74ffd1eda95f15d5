"""Pallas pack/unpack kernels for ARBITRARY bit widths 1–16 (§II-B).

The word-aligned kernels (``frac_pack.pack32``) only handle k | 32;
this module covers the fractional widths the FRAC degradation ladder
actually produces — ``bits_for(m, alpha)`` codewords like 11 bits in
7 three-state cells (m=3, α=7) — where codes straddle uint32
boundaries and a scatter would serialize.

Cross-word-carry layout
-----------------------
The packed stream repeats with period LCM(k, 32) bits.  One period —
a *segment* — holds ``c_seg = 32/gcd(k,32)`` codes in exactly
``w_seg = k/gcd(k,32)`` words, so segments are word-aligned and
self-contained: a code can straddle a word boundary inside its
segment, never the segment edge (the last code ends exactly on it).
Examples: k=11 → 32 codes in 11 words; k=3 → 32 codes in 3 words;
k=12 → 8 codes in 3 words; aligned k degenerate to w_seg = 1.

A tile is ``(T, c_seg)`` codes ↔ ``(T, w_seg)`` words, T segments per
grid cell.  ``codec.seg_layout(k)`` precomputes, per segment position:

  * pack:   for each word w, the static list of contributing codes —
    code j's lo part shifted left by ``(j·k) % 32`` into its start
    word, and, when ``(j·k) % 32 + k > 32``, its hi spill shifted
    right into the next word.  The kernel OR-accumulates these at
    trace time: per segment that is c_seg + (#straddlers) shift-ORs,
    fully unrolled, no scatter.
  * unpack: for each code j, its start word ``w0[j]``, shift, and
    (for straddlers) the carry from word ``w0[j]+1``.  The kernel
    reads both columns statically and shift-ORs the halves — the
    inverse carry, no gather.

Both kernels are bit-identical to ``core/frac/codec.py``'s
``pack_bits``/``unpack_bits`` (property-tested against the seed
scatter/gather oracle).  Note the division of labor: tensor consumers
go through the ``ops.encode_tensor``/``decode_tensor`` dispatch, whose
pallas modes run the *fused* quantize→pack / unpack→dequantize
pipelines in ``frac_quant_pack.py`` (same segment tables on (block,
segment, code) tiles) and whose jnp mode runs the codec's carry paths.
This module is the standalone words-only kernel pair for
already-quantized codes — the TPU candidate for ``ops.pack_codes``-
style payloads (e.g. the compressed all-reduce wire) once Mosaic
lowering is validated; until then it is exercised by the kernel parity
tests.

Like the word-aligned kernels, these are validated in interpret mode
and via the jnp dispatch fallback; Mosaic lowering on real TPU
hardware is still pending (the lane axis c_seg ≤ 32 is narrower than
the 128-lane VPU — see ROADMAP).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.frac.codec import seg_geometry, seg_layout

TILE_SEGS = 512          # segments per grid cell (≤ 64 KiB code words)

SUPPORTED_K = tuple(range(1, 17))


def _pack_kernel(codes_ref, o_ref, *, k: int):
    """(T, c_seg) codes -> (T, w_seg) words via the static carry table."""
    _, _, _, contrib = seg_layout(k)
    _, w_seg = seg_geometry(k)
    codes = codes_ref[...]
    cols = []
    for w in range(w_seg):
        acc = None
        for j, s, is_hi in contrib[w]:
            term = (codes[:, j] >> np.uint32(s)) if is_hi \
                else (codes[:, j] << np.uint32(s))
            acc = term if acc is None else acc | term
        cols.append(acc)
    o_ref[...] = jnp.stack(cols, axis=1)


def _unpack_kernel(words_ref, o_ref, *, k: int):
    """(T, w_seg) words -> (T, c_seg) codes, inverse carry."""
    w0, shift, spill, _ = seg_layout(k)
    c_seg, _ = seg_geometry(k)
    mask = jnp.uint32((1 << k) - 1)
    words = words_ref[...]
    cols = []
    for j in range(c_seg):
        v = words[:, w0[j]] >> np.uint32(shift[j])
        if spill[j]:
            v = v | (words[:, w0[j] + 1] << np.uint32(32 - shift[j]))
        cols.append(v & mask)
    o_ref[...] = jnp.stack(cols, axis=1)


def _pad_rows(a: jax.Array, rows: int) -> jax.Array:
    extra = rows - a.shape[0]
    if extra:
        a = jnp.pad(a, ((0, extra), (0, 0)))
    return a


@partial(jax.jit, static_argnames=("k", "interpret"))
def pack_carry(codes: jax.Array, k: int, interpret: bool = False) -> jax.Array:
    """codes: (N,) uint32 < 2^k -> packed (ceil(N·k/32),) uint32, any
    k in 1..16.  Bit-identical to ``codec.pack_bits``."""
    assert k in SUPPORTED_K, f"pack_carry needs 1 <= k <= 16, got {k}"
    c_seg, w_seg = seg_geometry(k)
    n = codes.shape[0]
    n_words = -(-(n * k) // 32)
    n_seg = -(-n // c_seg)
    grid = pl.cdiv(n_seg, TILE_SEGS)
    gs = grid * TILE_SEGS
    v = jnp.pad(codes.astype(jnp.uint32), (0, gs * c_seg - n))
    words = pl.pallas_call(
        partial(_pack_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((gs, w_seg), jnp.uint32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((TILE_SEGS, c_seg), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_SEGS, w_seg), lambda i: (i, 0)),
        interpret=interpret,
    )(v.reshape(gs, c_seg))
    return words.reshape(-1)[:n_words]


@partial(jax.jit, static_argnames=("k", "n", "interpret"))
def unpack_carry(words: jax.Array, k: int, n: int,
                 interpret: bool = False) -> jax.Array:
    """Inverse of pack_carry -> (n,) uint32."""
    assert k in SUPPORTED_K, f"unpack_carry needs 1 <= k <= 16, got {k}"
    c_seg, w_seg = seg_geometry(k)
    n_seg = -(-n // c_seg)
    grid = pl.cdiv(n_seg, TILE_SEGS)
    gs = grid * TILE_SEGS
    w = jnp.pad(words, (0, gs * w_seg - words.shape[0]))
    codes = pl.pallas_call(
        partial(_unpack_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((gs, c_seg), jnp.uint32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((TILE_SEGS, w_seg), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_SEGS, c_seg), lambda i: (i, 0)),
        interpret=interpret,
    )(w.reshape(gs, w_seg))
    return codes.reshape(-1)[:n]
