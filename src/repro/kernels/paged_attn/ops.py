"""Dispatch point for fused paged-attention decode.

``transformer._attn_decode_paged`` (behind the engine's
``paged_kernel=True`` flag) and the serve benchmarks call
``paged_attention`` here; backend selection lives in exactly one place:

  mode="pallas"  compiled Pallas page-walk kernel (paged_decode.py) —
                 per-lane trip count, pages DMA'd from HBM, TPU only.
  mode="pallas_interpret"
                 same kernel through the Pallas interpreter (tests /
                 CPU debugging; slow but bit-comparable to "jnp").
  mode="jnp"     vectorized page walk: a ``fori_loop`` over page
                 columns bounded by ``max(pos) // ps + 1`` across the
                 bucket (a traced bound — XLA lowers it to a while
                 loop), one page-column chunk per step, the kernel's
                 own ``fold_chunk`` vmapped over lanes.  The transient
                 per step is ``(B, chunk*ps, K, hd)`` keys/values plus a
                 ``(B, K, G, chunk*ps)`` score tile — never the
                 ``(B, max_pages * ps, K, hd)`` gather.  The CPU path.
  mode=None      auto: "pallas" on TPU, else "jnp".  A kernel that
                 fails to compile raises; nothing falls back.

``REPRO_PAGED_ATTN_MODE`` overrides the auto choice for all consumers —
the serve engine doesn't expose the mode parameter, so this is the
operational escape hatch (same contract as ``REPRO_FRAC_MODE``).

Walked-but-masked pages are EXACT no-ops in the accumulator
(``r = exp(0) = 1``, ``p = exp(NEG_INF - m) = 0``), which is what lets
the jnp walk use one shared bucket-wide page bound while the Pallas
kernel walks per-lane counts: both produce the same per-page update
sequence for every lane.  The gather + ``common.attention`` oracle
stays the ground truth for tests (see paged_decode.py docstring for
why oracle equality is token-level, not float-bit-level).

``gather_transient_bytes`` / ``kernel_transient_bytes`` model the peak
per-layer attention transient of each read path; the serve engine
stamps them into ``ServeStats.attn_transient_peak`` and the CI bench
gate asserts kernel < gather on the skewed long-context fixture.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.paged_attn import paged_decode

NEG_INF = paged_decode.NEG_INF
VALID_MODES = ("pallas", "pallas_interpret", "jnp")
ENV_VAR = "REPRO_PAGED_ATTN_MODE"


def default_mode() -> str:
    """Auto backend selection (env override, then platform)."""
    forced = os.environ.get(ENV_VAR)
    if forced:
        if forced not in VALID_MODES:
            raise ValueError(
                f"{ENV_VAR}={forced!r}: expected one of "
                + " | ".join(VALID_MODES))
        return forced
    if jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


def _resolve_mode(mode: str | None) -> str:
    """An explicit mode wins; otherwise the env var, then the platform.
    Nothing falls back: a kernel that fails to compile raises."""
    if mode is None:
        return default_mode()
    if mode not in VALID_MODES:
        raise ValueError(
            f"mode={mode!r}: expected one of " + " | ".join(VALID_MODES))
    return mode


def _paged_attention_jnp(q, pk, pv, page_table, pos, layer, chunk):
    """Vectorized page walk: the kernel's ``fold_chunk`` vmapped over
    lanes, one page-table chunk per step, each chunk's pages gathered
    from layer ``layer`` of the stacked pools.  ``page_table`` width is
    a multiple of ``chunk`` (padded by the dispatcher)."""
    B, H, hd = q.shape
    ps, K = pk.shape[2], pk.shape[3] // hd
    G = H // K
    T = chunk * ps
    max_pages = page_table.shape[1]
    qg = paged_decode.scaled_query(q, K)
    pos = pos.astype(jnp.int32)
    n_pages = jnp.minimum(jnp.max(pos) // ps + 1, max_pages)
    n_chunks = (n_pages + chunk - 1) // chunk
    slot = jnp.arange(T)                         # slot offset in chunk
    fold = jax.vmap(paged_decode.fold_chunk)

    def body(t, carry):
        first = t * chunk
        entries = jax.lax.dynamic_slice_in_dim(
            page_table, first, chunk, axis=1)           # (B, chunk)
        pids = jnp.maximum(entries, 0)
        k = pk[layer, pids].reshape(B, T, K * hd)
        v = pv[layer, pids].reshape(B, T, K * hd)
        valid = ((first * ps + slot)[None, :] <= pos[:, None]) \
            & (entries[:, slot // ps] > 0)              # (B, T)
        return tuple(
            fold(qg[:, h], k[:, :, h * hd:(h + 1) * hd],
                 v[:, :, h * hd:(h + 1) * hd], valid[:, None, :],
                 valid[:, :, None], *carry[h])
            for h in range(K))

    carry = jax.lax.fori_loop(0, n_chunks, body,
                              paged_decode.init_carry(K, G, hd, (B,)))
    out = jnp.stack([acc / jnp.maximum(l, 1.0) for _, l, acc in carry],
                    axis=1)                             # (B, K, G, hd)
    return out.reshape(B, H, hd).astype(q.dtype)


PAGES_PER_CHUNK = 4      # pages folded per accumulator step: amortizes
                         # the loop-dispatch overhead of the walk while
                         # keeping the transient a small constant
                         # multiple of one page (never the table width)


def paged_attention(q: jax.Array,           # (B, H, hd)
                    pk: jax.Array,          # (L, P, ps, K*hd)
                    pv: jax.Array,
                    page_table: jax.Array,  # (B, max_pages)
                    pos: jax.Array,         # (B,)
                    layer: jax.Array,       # int32 scalar
                    *, mode: str | None = None,
                    chunk: int = PAGES_PER_CHUNK) -> jax.Array:
    """Fused paged GQA decode attention over layer ``layer`` of the
    stacked lane-dense pools; (B, H, hd) in q.dtype.

    Any chunk size produces bit-identical output for a given mode
    (walked-but-masked pages are exact accumulator no-ops, and chunk
    boundaries only group the SAME per-page updates), and "jnp" ==
    "pallas"/"pallas_interpret" bit-for-bit at equal chunk."""
    mode = _resolve_mode(mode)
    max_pages = page_table.shape[1]
    chunk = max(1, min(chunk, max_pages))
    if max_pages % chunk:
        # pad with unallocated columns so chunks tile the table; -1
        # entries are masked to exact no-ops in the walk
        pad = chunk - max_pages % chunk
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)),
                             constant_values=-1)
    if mode == "jnp":
        return _paged_attention_jnp(q, pk, pv, page_table, pos, layer,
                                    chunk)
    return paged_decode.paged_attention(
        q, pk, pv, page_table, pos, layer, chunk=chunk,
        interpret=(mode == "pallas_interpret"))


# ---------------------------------------------------------------------------
# Peak attention-transient model (bytes per layer per decode step)
# ---------------------------------------------------------------------------

def gather_transient_bytes(B: int, max_pages: int, page_size: int,
                           K: int, G: int, hd: int,
                           kv_itemsize: int) -> int:
    """gather_pages read path: the full (B, max_pages*ps, K, hd) k AND
    v gathers coexist with the fp32 (B, K, G, 1, max_pages*ps) score
    block — every lane pays the bucket-max table width."""
    slots = max_pages * page_size
    kv = 2 * B * slots * K * hd * kv_itemsize
    scores = B * K * G * slots * 4
    return kv + scores


def kernel_transient_bytes(B: int, page_size: int,
                           K: int, G: int, hd: int,
                           kv_itemsize: int,
                           chunk: int = PAGES_PER_CHUNK) -> int:
    """Fused page walk: one (B, chunk*ps, K, hd) k/v page-column
    chunk, the fp32 (B, K, G, chunk*ps) score tile, and the
    (m, l, acc) accumulator — independent of the bucket's table
    width."""
    slots = chunk * page_size
    kv = 2 * B * slots * K * hd * kv_itemsize
    scores = B * K * G * slots * 4
    accum = B * K * G * (hd + 2) * 4
    return kv + scores + accum
