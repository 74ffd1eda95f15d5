"""Pallas paged-attention decode kernel: attend through the page table.

One grid step per lane.  The lane's page-table row, its decode
position and the layer index arrive as scalar prefetch (SMEM); the
stacked K/V pools of every layer stay in HBM (``pl.ANY``) and are read
in place: a page is the DMA of ``pool.at[layer, page]``, so neither the
layer's pool nor the stack is ever sliced, reshaped or copied outside
the kernel.  The kernel walks ONLY the lane's
``ceil((pos+1)/page_size)`` pages, ``chunk`` pages per step: each step
DMAs the next chunk's pages into the other half of a double-buffered
VMEM scratch while it folds the current chunk into a running
flash-attention accumulator ``(m, l, acc)`` per KV head.  The gathered
contiguous ``(B, max_pages * page_size, K, hd)`` cache that
``common.gather_pages`` materializes never exists, and VMEM holds two
chunks of pages whatever the pool size.

Layout: a pool leaf is stored lane-dense as ``(L, P, ps, K*hd)``
(``transformer.paged_pool_specs``), the kernel's own layout, so one
page is one contiguous DMA and KV head ``h`` is the column slice
``[h*hd, (h+1)*hd)`` of the chunk buffer.  The query arrives
pre-scaled as ``(B, K, G, hd)``.

Index math (mirrors serve/paging.py's layout):

  logical slot s of lane b  ->  pool[layer, page_table[b, s // ps], s % ps]
  pages to walk             ->  n = min(pos // ps + 1, max_pages)
  slot validity in page i   ->  (i * ps + arange(ps) <= pos)
                                 & (page_table[b, i] > 0)

Page-table entries are ``-1`` when unallocated and ``0`` is the
reserved trash page (serve/paging.py ``TRASH_PAGE``); both are invalid
for reads, so validity is ``entry > 0`` (invalid entries DMA the trash
page, whose contents never matter).  Invalid slots get a ``NEG_INF``
score (softmax weight 0) AND their value rows are zeroed with
``jnp.where`` before the weighted sum — a NaN/inf-poisoned trash page
must not leak through ``0 * NaN`` (locked by the poisoned-pool test in
tests/test_serve_paged.py).

Online-softmax update per chunk and KV head (all fp32, ``fold_chunk``):

  m' = max(m, max_s)          r = exp(m - m')
  p  = exp(s - m')            l' = l * r + sum(p)
  acc' = acc * r + p @ v      out = acc / l      (l >= 1 for live lanes)

A fully-masked lane (dead: every entry <= 0) keeps ``acc == 0``; the
epilogue divides by ``max(l, 1)`` so its output is exact zeros —
garbage-but-finite, same contract as the gather oracle, and the serve
loop discards dead lanes' tokens anyway.

``fold_chunk`` is shared with the ``jnp`` walk in ops.py (which vmaps
it over lanes), so interpret-mode runs are bit-comparable against it;
the gather + ``common.attention`` oracle differs in reduction ORDER
(full-row softmax, probs cast to the value dtype before the weighted
sum), so kernel-vs-oracle equality is asserted within a tolerance,
not at float-bit level.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite, matches common.NEG_INF: masked != NaN
_HI = jax.lax.Precision.HIGHEST


def scaled_query(q: jax.Array, num_kv_heads: int) -> jax.Array:
    """(B, H, hd) -> (B, K, G, hd) scaled in the input dtype, exactly
    like the oracle's ``q.reshape(...) * hd**-0.5`` (common.attention)."""
    B, H, hd = q.shape
    return (q * (hd ** -0.5)).reshape(B, num_kv_heads, H // num_kv_heads, hd)


def fold_chunk(q, k, v, valid_row, valid_col, m, l, acc):
    """Fold one chunk of one KV head into the accumulator.

    q (G, hd); k, v (T, hd); valid_row (1, T) / valid_col (T, 1) bool;
    m, l (G, 1) fp32; acc (G, hd) fp32."""
    # fp32 operands get the exact multi-pass MXU product; Mosaic takes
    # no precision for bf16 operands (their product is exact anyway)
    s = jnp.einsum("gh,th->gt", q, k,
                   precision=_HI if q.dtype == jnp.float32 else None,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid_row, s, NEG_INF)
    v = jnp.where(valid_col, v, jnp.zeros((), v.dtype))
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    r = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = l * r + p.sum(axis=-1, keepdims=True)
    acc = acc * r + jnp.einsum("gt,th->gh", p, v.astype(jnp.float32),
                               precision=_HI,
                               preferred_element_type=jnp.float32)
    return m_new, l, acc


def init_carry(K: int, G: int, hd: int, lead: tuple = ()):
    return tuple(
        (jnp.full((*lead, G, 1), NEG_INF, jnp.float32),
         jnp.zeros((*lead, G, 1), jnp.float32),
         jnp.zeros((*lead, G, hd), jnp.float32))
        for _ in range(K))


def _paged_attn_kernel(pt_ref, pos_ref, layer_ref, q_ref, pk_hbm, pv_hbm,
                       o_ref, kbuf, vbuf, sem, *, page_size: int, chunk: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    _, K, G, hd = q_ref.shape
    ps = page_size
    T = chunk * ps
    max_pages = pt_ref.shape[1]          # padded to a multiple of chunk
    pos = pos_ref[b]
    n_pages = jnp.minimum(pos // ps + 1, max_pages)
    n_chunks = (n_pages + chunk - 1) // chunk

    def copies(t, slot):
        out = []
        for j in range(chunk):
            pid = jnp.maximum(pt_ref[b, t * chunk + j], 0)
            dst = pl.ds(j * ps, ps)
            out.append(pltpu.make_async_copy(
                pk_hbm.at[layer, pid], kbuf.at[slot, dst], sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                pv_hbm.at[layer, pid], vbuf.at[slot, dst], sem.at[1, slot]))
        return out

    for cp in copies(0, 0):
        cp.start()

    def body(t, carry):
        slot = t % 2

        @pl.when(t + 1 < n_chunks)
        def _():
            for cp in copies(t + 1, 1 - slot):
                cp.start()

        for cp in copies(t, slot):
            cp.wait()
        first = t * chunk
        row = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)

        def valid(s_ids):
            ok = s_ids < 0                       # all False
            for j in range(chunk):
                live = pt_ref[b, first + j] > 0
                ok = ok | ((s_ids >= j * ps) & (s_ids < (j + 1) * ps) & live)
            return ok & (first * ps + s_ids <= pos)

        vrow, vcol = valid(row), valid(col)
        kc, vc = kbuf[slot], vbuf[slot]          # (T, K*hd)
        return tuple(
            fold_chunk(q_ref[0, h], kc[:, h * hd:(h + 1) * hd],
                       vc[:, h * hd:(h + 1) * hd], vrow, vcol, *carry[h])
            for h in range(K))

    carry = jax.lax.fori_loop(0, n_chunks, body, init_carry(K, G, hd))
    for h, (_, l, acc) in enumerate(carry):
        o_ref[0, h] = (acc / jnp.maximum(l, 1.0)).astype(o_ref.dtype)


def paged_attention(q: jax.Array,          # (B, H, hd) decode query
                    pk: jax.Array,         # (L, P, ps, K*hd) stacked pool
                    pv: jax.Array,
                    page_table: jax.Array,  # (B, max_pages) int32,
                                            # max_pages % chunk == 0
                    pos: jax.Array,         # (B,) int32 decode positions
                    layer: jax.Array,       # int32 scalar: the pool's layer
                    *, chunk: int = 1, interpret: bool = False) -> jax.Array:
    """Fused paged GQA decode attention over layer ``layer`` of the
    stacked pools.  Returns (B, H, hd) in q.dtype.

    ``chunk`` pages fold into the accumulator per loop step (ops.py
    pads the table so it divides ``max_pages``): the per-iteration
    einsum grows, the trip count shrinks — the accumulator sequence is
    unchanged up to exact no-op pages, so any chunk size is
    bit-identical to the matching jnp walk."""
    B, H, hd = q.shape
    ps, lanes = pk.shape[2], pk.shape[3]
    K = lanes // hd
    max_pages = page_table.shape[1]
    if H % K or lanes != K * hd or max_pages % chunk:
        raise ValueError(f"heads {H} % kv_heads {K}, pool lanes {lanes} "
                         f"% head_dim {hd}, table width {max_pages} % "
                         f"chunk {chunk} must all be 0")
    G = H // K
    qg = scaled_query(q, K)
    blk = pl.BlockSpec((1, K, G, hd), lambda b, pt, pos, layer: (b, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        partial(_paged_attn_kernel, page_size=ps, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[blk, pool, pool],
            out_specs=blk,
            scratch_shapes=[
                pltpu.VMEM((2, chunk * ps, lanes), pk.dtype),
                pltpu.VMEM((2, chunk * ps, lanes), pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, pk, pv)
    return out.reshape(B, H, hd)
