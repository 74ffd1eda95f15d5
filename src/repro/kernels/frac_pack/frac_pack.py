"""Pallas TPU kernel: FRAC bit-pack/unpack hot path (paper §II-B).

The checkpoint/optimizer-state/grad-compression paths move billions of
k-bit codes per step; this kernel packs them into uint32 words with pure
VPU shift/or traffic, tiled so each grid cell stays in VMEM.  It covers
the word-aligned codes (k ∈ {2, 4, 8, 16}); fractional-bit codewords
(the 11-bits-in-7-cells cases) take the cross-word-carry kernel pair in
``frac_carry_pack.py``, which handles every width 1–16.  The jnp codec
(core/frac/codec.py) is both kernels' oracle.

Memory-bound by design: the roofline win is that checkpoint bytes drop
k/32-fold before they ever leave HBM.

This module packs ALREADY-QUANTIZED codes; the fused quantize→pack
pipeline (absmax scale + quantize + pack in one VMEM pass) lives in
``frac_quant_pack.py``, and consumers should go through the
``ops.encode_tensor``/``decode_tensor`` dispatch rather than calling
either kernel directly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024          # words per grid cell


def _pack_kernel(codes_ref, o_ref, *, k: int):
    c = 32 // k
    codes = codes_ref[...]                        # (tile, c) uint32
    word = jnp.zeros_like(codes[:, 0])
    for j in range(c):
        word = word | (codes[:, j] << (k * j))
    o_ref[...] = word


def _unpack_kernel(words_ref, o_ref, *, k: int):
    c = 32 // k
    words = words_ref[...]                        # (tile,) uint32
    mask = jnp.uint32((1 << k) - 1)
    cols = [ (words >> (k * j)) & mask for j in range(c)]
    o_ref[...] = jnp.stack(cols, axis=1)          # (tile, c)


@partial(jax.jit, static_argnames=("k", "interpret"))
def pack32(codes: jax.Array, k: int, interpret: bool = False) -> jax.Array:
    """codes: (N,) uint32 < 2^k, with (32/k) | N -> (N·k/32,) uint32."""
    assert 32 % k == 0, f"pack32 needs k | 32, got {k}"
    c = 32 // k
    n = codes.shape[0]
    assert n % c == 0, (n, c)
    n_words = n // c
    grid = max(1, n_words // TILE)
    tile = n_words // grid
    assert n_words % grid == 0
    return pl.pallas_call(
        partial(_pack_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((n_words,), jnp.uint32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        interpret=interpret,
    )(codes.reshape(n_words, c).astype(jnp.uint32))


@partial(jax.jit, static_argnames=("k", "n", "interpret"))
def unpack32(words: jax.Array, k: int, n: int, interpret: bool = False) -> jax.Array:
    """Inverse of pack32 -> (n,) uint32."""
    assert 32 % k == 0
    c = 32 // k
    n_words = words.shape[0]
    assert n == n_words * c, (n, n_words, c)
    grid = max(1, n_words // TILE)
    tile = n_words // grid
    assert n_words % grid == 0
    out = pl.pallas_call(
        partial(_unpack_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((n_words, c), jnp.uint32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tile, c), lambda i: (i, 0)),
        interpret=interpret,
    )(words.astype(jnp.uint32))
    return out.reshape(n)
