"""Paged KV-cache machinery: page plan, device-side allocator, pool fill.

The serve engine's paged layout (serve/engine.py) replaces the
contiguous per-lane cache ``(B, bucket_max + horizon, K, hd)`` with a
shared **page pool** ``(L, P, page_size, K*hd)`` stacked over layers
(a slot's KV heads side by side, the paged kernel's layout) plus one
**page table** ``(B, max_pages)`` shared by every layer (all layers
grow in lockstep, so one allocation covers the whole stack).  Logical
position ``p`` of lane ``b`` lives at
``pool[layer, page_table[b, p // page_size], p % page_size]``.

Conventions (shared by the jitted decode loop and the property tests):

  - page id ``0`` is the reserved **trash page**: it is never on the
    free list and absorbs every masked/dead-lane write, so predication
    never needs a branch;
  - valid page ids are ``1 .. n_pages-1``;
  - an unallocated page-table entry is ``-1``;
  - the free list is a stack: ``free_stack[:free_top]`` holds the free
    ids, pop from ``free_stack[free_top-1]``.

The conservation invariant the property suite locks
(tests/test_serve_paged.py): at every step
``free_top + pages-in-live-tables == n_pages - 1`` and no page id
appears in two live rows — allocation is exact, freeing returns every
page exactly once, the trash page is never handed out.

All three in-loop primitives (:func:`alloc_pages`,
:func:`free_lane_pages`) are branch-free jnp — masked scatters with
``mode="drop"`` — so they trace inside the engine's ``lax.while_loop``
/ ``fori_loop`` without ``lax.cond``.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

TRASH_PAGE = 0


def pages_for(n_slots: int, page_size: int) -> int:
    """Pages needed to hold ``n_slots`` KV rows."""
    return -(-int(n_slots) // int(page_size))


@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Host-side initial page layout for one serve super-bucket.

    ``page_table`` covers the ``n_active`` decode lanes, ``staged_pt``
    the pre-staged pending requests (their prompt pages are resident
    from t=0; a lane adopts the row at in-loop admission).  Both hold
    prompt pages only — decode growth allocates from ``free_stack``
    inside the loop.  ``n_pages`` is a tight safe capacity: at any
    instant every unfinished request holds at most its *prompt* pages
    while staged, and only ``n_active`` requests decode (grown toward
    their ``len + max_new`` horizon) at once, so
    ``1 + Σ prompt_pages + top-n_active(horizon − prompt)`` can never
    underflow — strictly less pool than the no-reuse worst case when
    the queue is deeper than the lane count.  ``pow2=True`` (the
    engine's default) rounds ``n_pages`` and ``max_pages`` up to
    powers of two — the spare pages just sit on the free stack — so
    the jitted loop compiles a bounded set of shape variants instead
    of one per request mix (the same trick as the engine's ``out_cap``
    rounding).
    """

    page_size: int
    n_pages: int                 # P, including the trash page
    max_pages: int               # MP, page-table width
    page_table: np.ndarray       # (n_active, MP) int32
    staged_pt: np.ndarray        # (n_staged, MP) int32
    free_stack: np.ndarray       # (P,) int32
    free_top: int
    prompt_pages: np.ndarray     # (R,) int32, pages initially held per request


def plan_pages(lens, max_new, n_active: int, page_size: int,
               pow2: bool = False) -> PagePlan:
    lens = np.asarray(lens, np.int64)
    max_new = np.asarray(max_new, np.int64)
    assert lens.shape == max_new.shape and lens.min() >= 1
    horizon = np.asarray(
        [pages_for(l + m, page_size) for l, m in zip(lens, max_new)], np.int64)
    prompt = np.asarray([pages_for(l, page_size) for l in lens], np.int64)
    mp = int(horizon.max())
    grow = np.sort(horizon - prompt)[::-1]
    n_pages = 1 + int(prompt.sum()) + int(grow[:n_active].sum())
    if pow2:
        mp = 1 << (mp - 1).bit_length()
        n_pages = 1 << (n_pages - 1).bit_length()
    table = np.full((len(lens), mp), -1, np.int32)
    nxt = 1
    for i, npg in enumerate(prompt):
        table[i, :npg] = np.arange(nxt, nxt + npg, dtype=np.int32)
        nxt += int(npg)
    free_ids = np.arange(nxt, n_pages, dtype=np.int32)
    free_stack = np.zeros((n_pages,), np.int32)
    free_stack[: free_ids.size] = free_ids
    return PagePlan(
        page_size=page_size, n_pages=n_pages, max_pages=mp,
        page_table=table[:n_active], staged_pt=table[n_active:],
        free_stack=free_stack, free_top=int(free_ids.size),
        prompt_pages=prompt.astype(np.int32),
    )


# ---------------------------------------------------------------------------
# In-loop primitives (pure jnp, branch-free)
# ---------------------------------------------------------------------------


def alloc_pages(page_table, free_stack, free_top, need, cols):
    """Pop one page per lane in ``need`` and record it at
    ``(lane, cols[lane])``.

    ``need`` (B,) bool, ``cols`` (B,) int32.  Lanes pop in lane order
    from the top of the stack.  Returns
    ``(page_table, free_top, n_allocated)``.  The caller guarantees
    capacity (PagePlan sizes the pool for the no-reuse worst case), so
    underflow cannot happen in the engine; indices are clipped anyway
    so a misuse corrupts data rather than faulting.
    """
    b = page_table.shape[0]
    order = jnp.cumsum(need.astype(jnp.int32)) - 1            # (B,)
    take = jnp.clip(free_top - 1 - order, 0, free_stack.shape[0] - 1)
    new_ids = free_stack[take]
    rows = jnp.arange(b)
    cols = jnp.clip(cols, 0, page_table.shape[1] - 1)
    cur = page_table[rows, cols]
    page_table = page_table.at[rows, cols].set(
        jnp.where(need, new_ids, cur))
    m = need.astype(jnp.int32).sum()
    return page_table, free_top - m, m


def free_lane_pages(row, free_stack, free_top, enable):
    """Push every allocated page id of ``row`` (MP,) back on the stack
    when ``enable`` (scalar bool); no-op otherwise.  Returns
    ``(cleared_row, free_stack, free_top, n_freed)`` — the cleared row
    is all ``-1`` when enabled, untouched otherwise."""
    allocated = (row > TRASH_PAGE) & enable
    order = jnp.cumsum(allocated.astype(jnp.int32)) - 1
    idx = jnp.where(allocated, free_top + order, free_stack.shape[0])
    free_stack = free_stack.at[idx].set(row, mode="drop")
    n = allocated.astype(jnp.int32).sum()
    row = jnp.where(enable, jnp.full_like(row, -1), row)
    return row, free_stack, free_top + n, n


# ---------------------------------------------------------------------------
# Prefill → pool scatter
# ---------------------------------------------------------------------------


def pool_scatter_indices(full_table: np.ndarray, lens, seq_len: int,
                         n_pages: int, page_size: int):
    """Flat (page, slot) scatter targets routing each lane's prefill
    rows into its pages.

    ``full_table`` is the (R, MP) table over *all* requests (active
    rows stacked over staged rows).  Pad rows (``s >= lens[b]``) are
    routed to index ``n_pages`` — out of bounds, dropped by the
    ``mode="drop"`` scatter — so right-padded prefill garbage never
    lands in a page.  Host-side numpy: the plan is static per bucket.
    """
    lens = np.asarray(lens, np.int64)
    r, mp = full_table.shape
    s = np.arange(seq_len)
    cols = np.minimum(s // page_size, mp - 1)                 # (S,)
    pi = full_table[:, cols].astype(np.int64)                 # (R, S)
    valid = (s[None, :] < lens[:, None]) & (pi > TRASH_PAGE)
    pi = np.where(valid, pi, n_pages)
    oi = np.broadcast_to(s % page_size, (r, seq_len))
    return pi.reshape(-1).astype(np.int32), oi.reshape(-1).astype(np.int32)


def fill_pool(pool_leaf, prefill_leaf, page_idx, slot_idx):
    """Scatter a prefill cache leaf ``(L, R, S, K, hd)`` into a pool
    leaf ``(L, P, page_size, K*hd)`` at the precomputed flat targets
    (see :func:`pool_scatter_indices`); each row's heads are merged
    into the pool's lane-dense slot."""
    l = prefill_leaf.shape[0]
    vals = prefill_leaf.reshape(l, -1, pool_leaf.shape[-1])
    return pool_leaf.at[:, page_idx, slot_idx].set(vals, mode="drop")
