"""Single dispatch point for the fused FRAC quantize→pack pipeline.

Every consumer of FRAC tensor encoding — the checkpoint manager
(``train/checkpoint.py``), gradient compression (``train/grad_compress``,
both ``ef_compress`` numerics and the ``compressed_allreduce_mean`` wire
payload), the frac8 optimizer state (``train/optimizer.py``) and the
serving engine's FRAC KV-cache option (``serve/engine.py``) — goes
through this module, so backend selection lives in exactly one place:

  mode="pallas"  fused Pallas kernel (frac_quant_pack.py), compiled
                 (interpret=False) on TPU — one HBM pass, packed output.
  mode="pallas_interpret"
                 same kernel through the Pallas interpreter (tests/CPU
                 debugging; slow but bit-exact).
  mode="jnp"     fused jnp path: quantize_blocks + the scatter-free
                 pack from core/frac/codec.py (shift-OR for aligned k,
                 segment cross-word carry for fractional k) in one jit;
                 decode runs as a fused elementwise stage plus a
                 reshape stage (XLA's CPU backend will not fuse
                 through the flat reshape, so splitting it keeps the
                 unpack→dequantize pass at memory bandwidth).  The
                 fast fallback wherever Mosaic isn't available.
  mode=None      auto: "pallas" on TPU, else "jnp" — for EVERY width
                 1..16; fractional widths (32 % k != 0) use the same
                 kernels via the cross-word-carry segment layout.  A
                 kernel that fails to compile raises; nothing falls
                 back to jnp behind the caller.

All modes produce bit-identical blobs ({"words", "scales", "meta"},
same schema as ``codec.frac_encode_tensor``), with the pure-jnp codec
as the property-tested oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.frac import codec
from repro.kernels.frac_pack import frac_quant_pack

Blob = dict[str, Any]

VALID_MODES = ("pallas", "pallas_interpret", "jnp")


def default_mode(kbits: int) -> str:
    """Auto backend selection.  ``REPRO_FRAC_MODE`` (pallas | jnp |
    pallas_interpret) overrides for all consumers — none of them expose
    the mode parameter, so this is the operational escape hatch."""
    import os

    forced = os.environ.get("REPRO_FRAC_MODE")
    if forced:
        if forced not in VALID_MODES:
            raise ValueError(
                f"REPRO_FRAC_MODE={forced!r}: expected one of "
                + " | ".join(VALID_MODES))
        if forced.startswith("pallas") \
                and kbits not in frac_quant_pack.SUPPORTED_K:
            # the env var is a global preference: widths outside the
            # kernels' 1..16 range still route to jnp
            return "jnp"
        return forced
    if kbits in frac_quant_pack.SUPPORTED_K \
            and jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


def _resolve_mode(kbits: int, mode: str | None) -> str:
    """Shared encode/decode mode resolution.  An explicitly passed
    pallas mode on a width the kernels do not cover raises; nothing
    ever switches backend behind the caller — a kernel that fails to
    compile raises where it is called."""
    if mode is None:
        return default_mode(kbits)
    if mode not in VALID_MODES:
        raise ValueError(
            f"mode={mode!r}: expected one of " + " | ".join(VALID_MODES))
    if mode.startswith("pallas") \
            and kbits not in frac_quant_pack.SUPPORTED_K:
        raise ValueError(
            f"mode={mode!r} requires 1 <= k <= 16 "
            f"(fused kernels cover every such width, fractional "
            f"included), got k={kbits}")
    return mode


# ---------------------------------------------------------------------------
# fused jnp path (one jit: XLA fuses quantize + shift-OR pack)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kbits",))
def _encode_jnp(flat, kbits: int):
    codes, scales = codec.quantize_blocks(flat, kbits)
    return codec.pack_bits(codes, kbits), scales


@partial(jax.jit, static_argnames=("kbits", "rng_source"))
def _encode_jnp_rng(flat, rng, kbits: int, rng_source: str = "uniform"):
    codes, scales = codec.quantize_blocks(flat, kbits, rng=rng,
                                          rng_source=rng_source)
    return codec.pack_bits(codes, kbits), scales


@partial(jax.jit, static_argnames=("kbits",))
def _decode_jnp_blocks(words, scales, kbits: int):
    """Fused unpack→dequantize -> (n_blocks, S, c_seg) fp32.

    Kept in block layout on purpose: one elementwise pass from packed
    words to dequantized floats (bit-identical arithmetic to
    ``codec.dequantize_blocks``).  The flat reshape happens in
    ``_finish_decode`` — XLA's CPU backend treats a reshaped output as
    a fusion root and would serialize this whole pass behind it,
    costing ~3x; two stages keep the heavy pass at memory bandwidth."""
    q = (1 << kbits) - 1
    nb = scales.shape[0]
    S, c_seg, w_seg = frac_quant_pack.block_layout(kbits)
    inv_q = float(np.float32(1.0) / np.float32(q))
    sc = scales[:, None, None] * inv_q
    if w_seg == 1:
        # aligned: every word holds c_seg whole codes, broadcast shift
        shifts = (jnp.arange(c_seg, dtype=jnp.uint32) * kbits)[None, None, :]
        w3 = words.reshape(nb, S, 1)
        cb = ((w3 >> shifts) & jnp.uint32(q)).astype(jnp.float32)
        return (cb * 2.0 - q) * sc
    # fractional: the shared static cross-word-carry unpack
    # (codec.carry_unpack_segments) — a take per code column plus
    # shift-ORs, one segment row per LCM(k,32)-bit period
    vals = codec.carry_unpack_segments(words.reshape(nb * S, w_seg), kbits)
    cb = vals.astype(jnp.float32).reshape(nb, S, c_seg)
    return (cb * 2.0 - q) * sc


@partial(jax.jit, static_argnames=("shape", "dtype", "n"))
def _finish_decode(x3, shape: tuple, dtype: str, n: int):
    flat = x3.reshape(-1)
    if n != flat.shape[0]:
        flat = flat[:n]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# tensor blobs
# ---------------------------------------------------------------------------


def encode_tensor(x: jax.Array, kbits: int = 8, *,
                  rng: jax.Array | None = None,
                  rng_source: str = "uniform",
                  mode: str | None = None) -> Blob:
    """Tensor -> FRAC blob via the fused pipeline.  Bit-identical to
    ``codec.frac_encode_tensor`` for every mode and every k.
    ``rng_source="trg"`` opts the stochastic rounding into the Amoeba
    TRG's counter-corrected bit stream (jnp path only — the Pallas
    kernel draws uniforms in-kernel)."""
    mode = _resolve_mode(kbits, mode)
    if rng_source not in codec.RNG_SOURCES:
        raise ValueError(
            f"rng_source={rng_source!r}: expected one of "
            + " | ".join(codec.RNG_SOURCES))
    if rng_source != "uniform" and mode.startswith("pallas"):
        raise ValueError(
            f"rng_source={rng_source!r} requires a jnp mode; "
            f"mode={mode!r} draws its uniforms in-kernel")
    flat = x.reshape(-1)
    n = flat.shape[0]
    if mode.startswith("pallas"):
        words, scales = frac_quant_pack.quant_pack(
            flat, kbits, rng=rng, interpret=(mode == "pallas_interpret"))
    else:
        flat = flat.astype(jnp.float32)
        if rng is None:
            words, scales = _encode_jnp(flat, kbits)
        else:
            words, scales = _encode_jnp_rng(flat, rng, kbits, rng_source)
    return {
        "words": words,
        "scales": scales,
        "meta": (tuple(x.shape), int(kbits), n, str(x.dtype)),
    }


def decode_tensor(blob: Blob, *, mode: str | None = None) -> jax.Array:
    """FRAC blob -> tensor (shape/dtype restored from meta)."""
    shape, kbits, n, dtype = blob["meta"]
    mode = _resolve_mode(kbits, mode)
    if mode.startswith("pallas"):
        flat = frac_quant_pack.unpack_dequant(
            blob["words"], blob["scales"], kbits, n,
            interpret=(mode == "pallas_interpret"))
        return flat.reshape(shape).astype(dtype)
    x3 = _decode_jnp_blocks(blob["words"], blob["scales"], kbits)
    return _finish_decode(x3, tuple(shape), dtype, n)


def frac_zeros_like(x: jax.Array, kbits: int = 8, *,
                    mode: str | None = None) -> Blob:
    return encode_tensor(jnp.zeros(x.shape, jnp.float32), kbits, mode=mode)


def compressed_bytes(blob: Blob) -> int:
    return codec.compressed_bytes(blob)


def compressed_nbytes(n: int, kbits: int) -> int:
    """Encoded size for n values at width kbits without building the
    blob (see core/frac/codec.compressed_nbytes)."""
    return codec.compressed_nbytes(n, kbits)


def compressed_nbytes_pages(n_pages: int, page_elems: int,
                            kbits: int) -> int:
    """Encoded size of a *paged* stream: ``n_pages`` independent runs
    of ``page_elems`` values each.  Pages are allocated and freed
    independently (serve/paging.py), so they can never share packed
    words or a trailing partial block — each page is booked as its own
    ``compressed_nbytes`` stream.  This is the serve engine's byte
    model for the paged FRAC KV tier: resident bytes scale with pages
    actually allocated, not with the bucket-max horizon."""
    return n_pages * codec.compressed_nbytes(page_elems, kbits)


# ---------------------------------------------------------------------------
# fake-quant (quantize→dequantize, no packed bytes materialized):
# ef_compress numerics and the emulated FRAC KV cache
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kbits",))
def _fake_quant_jnp(flat, kbits: int):
    codes, scales = codec.quantize_blocks(flat, kbits)
    return codec.dequantize_blocks(codes, scales, kbits, flat.shape[0])


@partial(jax.jit, static_argnames=("kbits",))
def _fake_quant_jnp_rng(flat, rng, kbits: int):
    codes, scales = codec.quantize_blocks(flat, kbits, rng=rng)
    return codec.dequantize_blocks(codes, scales, kbits, flat.shape[0])


def fake_quant(x: jax.Array, kbits: int, *,
               rng: jax.Array | None = None) -> jax.Array:
    """x -> dequantize(quantize(x)), same shape/dtype.  Numerically
    identical to a full encode→decode round trip (packing is lossless),
    without materializing the packed words."""
    flat = x.reshape(-1).astype(jnp.float32)
    if rng is None:
        out = _fake_quant_jnp(flat, kbits)
    else:
        out = _fake_quant_jnp_rng(flat, rng, kbits)
    return out.reshape(x.shape).astype(x.dtype)


def fake_quant_tree(tree: Any, kbits: int) -> Any:
    """fake_quant on every floating leaf of a pytree (KV caches)."""
    def one(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return fake_quant(leaf, kbits)
        return leaf
    return jax.tree.map(one, tree)


def fake_quant_slots(x: jax.Array, kbits: int, *, row_dims: int = 1
                     ) -> jax.Array:
    """Row-granular fake-quant: one symmetric absmax scale per row,
    where a row is the trailing ``row_dims`` axes flattened — the FRAC
    slot write unit (one token's (K, hd) KV per layer per sequence).

    Same arithmetic as ``codec.quantize_blocks``/``dequantize_blocks``
    with the scale block equal to the row, written as plain jnp so it
    traces inside jitted decode loops (serve/engine.py decodes with
    this applied to every cache write).  Row-confined scales mean a
    sequence's quantized cache never depends on which bucket neighbours
    it was batched with — batched serving stays bit-identical to solo
    serving.  The modeled byte cost stays ``compressed_nbytes`` on the
    leaf (the codec's canonical block geometry over the packed stream).
    """
    assert 1 <= row_dims < x.ndim or x.ndim == row_dims == 1
    q = (1 << kbits) - 1
    lead = x.shape[: x.ndim - row_dims]
    xf = x.reshape(*lead, -1).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) + 1e-12
    t = jnp.round((xf / scale + 1.0) * 0.5 * q)
    codes = jnp.clip(t, 0, q)
    inv_q = float(np.float32(1.0) / np.float32(q))
    out = (codes * 2.0 - q) * (scale * inv_q)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# raw code <-> word helpers (the compressed_allreduce wire payload;
# shard_map-safe pure functions)
# ---------------------------------------------------------------------------


def pack_codes(codes: jax.Array, kbits: int) -> jax.Array:
    """(N,) uint32 codes < 2^k -> packed uint32 words (scatter-free for
    every width: shift-OR when aligned, segment carry when not)."""
    return codec.pack_bits(codes, kbits)


def unpack_codes(words: jax.Array, kbits: int, n: int) -> jax.Array:
    """Inverse of pack_codes -> (n,) uint32 codes.  Gather-free and
    shard_map/vmap-safe for every width 1..32."""
    return codec.unpack_bits(words, kbits, n)


# ---------------------------------------------------------------------------
# host-side page streams (serve/flash_tier.py): raw page bytes <-> FRAC
# cell levels at a flash block's current m-state.  Pure numpy — spills
# and fault-ins happen at host-orchestrated bucket boundaries, and a
# per-(page, m) jit here would recompile for every page size the pool
# produces.  The codeword geometry is the lossless layer of
# core/frac/codec.py: b = bits_for(m, best_alpha(m)) data bits per α
# cells, so m picks CAPACITY (cells per byte), never fidelity — spilled
# KV pages come back bit-identical, which is what keeps the
# oversubscribed engine's outputs equal to solo serving.
# ---------------------------------------------------------------------------


def _np_pack_bits(vals: np.ndarray, bits: int) -> np.ndarray:
    """(N,) codeword values < 2^bits -> packed uint32 word stream."""
    n = int(vals.size)
    n_words = -(-(n * bits) // 32)
    start = np.arange(n, dtype=np.uint64) * np.uint64(bits)
    wi = (start // np.uint64(32)).astype(np.int64)
    off = start % np.uint64(32)
    sh = vals.astype(np.uint64) << off
    words = np.zeros(n_words + 1, np.uint32)  # +1: spill sink for the tail
    np.bitwise_or.at(words, wi, (sh & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    np.bitwise_or.at(words, wi + 1, (sh >> np.uint64(32)).astype(np.uint32))
    return words[:n_words]


def _np_unpack_bits(words: np.ndarray, bits: int, n: int) -> np.ndarray:
    """Inverse of ``_np_pack_bits`` -> (n,) uint32 codeword values."""
    w = np.concatenate([words.astype(np.uint64), np.zeros(1, np.uint64)])
    start = np.arange(n, dtype=np.uint64) * np.uint64(bits)
    wi = (start // np.uint64(32)).astype(np.int64)
    pair = w[wi] | (w[wi + 1] << np.uint64(32))
    mask = np.uint64((1 << bits) - 1)
    return ((pair >> (start % np.uint64(32))) & mask).astype(np.uint32)


def page_stream_geometry(nbytes: int, m: int) -> tuple[int, int, int]:
    """(alpha, b, n_cells) for an nbytes page stored on m-state cells at
    the best-utilization code point."""
    alpha = codec.best_alpha(m)
    b = codec.bits_for(m, alpha)
    return alpha, b, codec.cells_for_bytes(nbytes, m, alpha)


def bytes_to_levels_np(data: bytes, m: int) -> np.ndarray:
    """Raw page bytes -> (n_cells,) uint8 base-m cell levels (the flash
    program path: each b-bit codeword becomes α Vth states)."""
    alpha, b, n_cells = page_stream_geometry(len(data), m)
    buf = bytes(data)
    words = np.frombuffer(buf + b"\x00" * ((-len(buf)) % 4), np.uint32)
    n_cw = n_cells // alpha
    need = -(-(n_cw * b) // 32)
    if words.size < need:
        words = np.concatenate([words, np.zeros(need - words.size, np.uint32)])
    vals = _np_unpack_bits(words, b, n_cw).astype(np.uint64)
    digits = np.empty((n_cw, alpha), np.uint8)
    for i in range(alpha):
        digits[:, i] = (vals % m).astype(np.uint8)
        vals //= m
    return digits.reshape(-1)


def levels_to_bytes_np(levels: np.ndarray, m: int, nbytes: int) -> bytes:
    """Cell levels -> the original nbytes page (the flash read path).
    Total function even on corrupted levels: a misread digit vector can
    land outside the 2^b codeword range (the code's utilization gap),
    so values are masked to b bits — the result is then garbage, but
    *deterministic* garbage the checksum layer detects."""
    alpha, b, _ = page_stream_geometry(nbytes, m)
    grp = levels.astype(np.uint64).reshape(-1, alpha)
    weights = np.array([m ** i for i in range(alpha)], np.uint64)
    vals = (grp * weights).sum(axis=1) & np.uint64((1 << b) - 1)
    return _np_pack_bits(vals, b).tobytes()[:nbytes]
