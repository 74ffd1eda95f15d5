"""Decoder-only transformer covering the dense / moe / vlm / hybrid families.

Layers are stacked for ``lax.scan`` over *period blocks* so heterogeneous
interleaves stay scan-able (small HLO, bounded compile time at 512
devices):

  - uniform archs: period 1 (attn + mlp/moe)
  - llama4: period 2 (dense mlp layer, then MoE layer)
  - jamba: period 8 (7 mamba + 1 attention; MoE on odd layers)

Entry points: ``forward`` (train), ``prefill`` (forward + cache emit),
``decode_step`` (one token against a KV cache).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro import spans
from repro.configs.base import ModelConfig
from repro.models import mamba as mamba_mod
from repro.models.common import (
    LeafSpec,
    activate,
    apply_rope,
    attention,
    gather_pages,
    rms_norm,
    stacked,
    windowed_prefill_attention,
)

# ---------------------------------------------------------------------------
# Period-block layout
# ---------------------------------------------------------------------------


def block_period(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_period
    if cfg.is_moe and cfg.moe_interleave > 1:
        return cfg.moe_interleave
    return 1


def sublayer_kinds(cfg: ModelConfig) -> list[tuple[str, str]]:
    """[(mixer, mlp)] for each layer j inside a period block."""
    period = block_period(cfg)
    out = []
    for j in range(period):
        if cfg.family == "hybrid":
            mixer = "attn" if j == period - 1 else "mamba"
        else:
            mixer = "attn"
        if cfg.is_moe and (j % cfg.moe_interleave == cfg.moe_interleave - 1):
            mlp = "moe"
        else:
            mlp = "mlp"
        out.append((mixer, mlp))
    return out


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def attn_param_specs(cfg: ModelConfig) -> dict:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": LeafSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": LeafSpec((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": LeafSpec((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": LeafSpec((H, hd, D), ("heads", "head_dim", "embed")),
    }


def mlp_param_specs(cfg: ModelConfig) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    specs = {
        "w_up": LeafSpec((D, F), ("embed", "mlp")),
        "w_down": LeafSpec((F, D), ("mlp", "embed")),
    }
    if cfg.gated_mlp:
        specs["w_gate"] = LeafSpec((D, F), ("embed", "mlp"))
    return specs


def _block_specs(cfg: ModelConfig) -> dict:
    from repro.models.moe import moe_param_specs

    D = cfg.d_model
    block: dict[str, Any] = {}
    for j, (mixer, mlp) in enumerate(sublayer_kinds(cfg)):
        if mixer == "attn":
            block[f"attn_{j}"] = attn_param_specs(cfg)
        else:
            block[f"mamba_{j}"] = mamba_mod.mamba_param_specs(cfg)
        block[f"norm1_{j}"] = LeafSpec((D,), ("embed",), init="ones")
        if mlp == "moe":
            block[f"moe_{j}"] = moe_param_specs(cfg)
        else:
            block[f"mlp_{j}"] = mlp_param_specs(cfg)
        if not cfg.parallel_block:
            block[f"norm2_{j}"] = LeafSpec((D,), ("embed",), init="ones")
    return block


def param_specs(cfg: ModelConfig) -> dict:
    n_periods = cfg.num_layers // block_period(cfg)
    block = _block_specs(cfg)
    specs: dict[str, Any] = {
        "embed": LeafSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "layers": jax.tree.map(
            lambda s: stacked(n_periods, s),
            block,
            is_leaf=lambda x: isinstance(x, LeafSpec),
        ),
        "final_norm": LeafSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = LeafSpec(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab")
        )
    return specs


# ---------------------------------------------------------------------------
# Sub-layer application
# ---------------------------------------------------------------------------


def _qkv(x, ap, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_seq(x, ap, cfg: ModelConfig, *, causal=True, emit_cache=False):
    """Full-sequence attention sublayer.  x: (B, S, D)."""
    from repro.sharding.rules import active_layout, shard_hint

    B, S, D = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(x, ap, cfg, positions)
    W, c = cfg.sliding_window, cfg.attn_chunk
    if active_layout(cfg).startswith("sp"):
        # Ulysses-style: queries stay sequence-sharded; K/V are gathered
        # to full sequence (the per-layer all-gather is the SP cost).
        assert not W, "SP layout + sliding window not combined (no arch needs it)"
        k = shard_hint(k, "batch", "none", "none", "none")
        v = shard_hint(v, "batch", "none", "none", "none")
        out = attention(q, k, v, causal=causal, chunk=0,
                        scores_bf16=cfg.sp_scores_bf16)
    elif W and S > W + c:
        out = windowed_prefill_attention(q, k, v, window=W, chunk=c)
    else:
        out = attention(q, k, v, causal=causal, window=W, chunk=c)
    out = jnp.einsum("bshk,hkd->bsd", out, ap["wo"])
    if emit_cache:
        Sc = min(S, cfg.max_decode_window) if cfg.max_decode_window else S
        kc, vc = k[:, -Sc:], v[:, -Sc:]
        if Sc < S and S % Sc:
            # rolling cache invariant: position p lives at slot p % Sc
            kc = jnp.roll(kc, S % Sc, axis=1)
            vc = jnp.roll(vc, S % Sc, axis=1)
        return out, {"k": kc, "v": vc}
    return out


def _attn_decode(x, ap, cfg: ModelConfig, cache, pos, kv_kbits=None):
    """One-token attention against the cache.  x: (B, 1, D).

    ``pos`` is a scalar (uniform bucket) or a (B,) vector (ragged
    bucket: each sequence sits at its own absolute position, writes its
    own cache slot, and masks its own valid span).  ``kv_kbits``
    fake-quantizes the newly written KV slot through the FRAC pipeline
    *inside* the decode loop — decode-written cache rows then carry
    exactly the fidelity a k-bit cell array would return, same as the
    prefill rows (serve/engine.py's FRAC KV tier).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    pos = jnp.asarray(pos)
    ragged = pos.ndim > 0
    ppos = pos[:, None] if ragged else jnp.full((1,), pos)  # (B,1) | (1,)
    q = apply_rope(q, ppos, cfg.rope_theta)
    k = apply_rope(k, ppos, cfg.rope_theta)
    if kv_kbits is not None:
        from repro.kernels.frac_pack import ops as fops

        # slot-granular (one scale per sequence's (K, hd) row): a lane's
        # quantization never depends on its bucket neighbours, so ragged
        # batched serving stays bit-identical to solo serving
        k = fops.fake_quant_slots(k, kv_kbits, row_dims=2)
        v = fops.fake_quant_slots(v, kv_kbits, row_dims=2)
    S_cache = cache["k"].shape[1]
    slot = pos % S_cache if cfg.max_decode_window else jnp.minimum(pos, S_cache - 1)
    if ragged:
        # per-sequence slot write: vmapped DUS lowers to an in-place
        # scatter, keeping the append O(1) in cache length
        upd = jax.vmap(
            lambda c, u, s: lax.dynamic_update_slice_in_dim(c, u, s, axis=0))
        ck = upd(cache["k"], k, slot)
        cv = upd(cache["v"], v, slot)
    else:
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    valid = jnp.minimum(pos + 1, S_cache)                # (B,) when ragged
    out = attention(
        q, ck, cv, causal=False, kv_valid_len=valid, q_positions=ppos
    )
    out = jnp.einsum("bshk,hkd->bsd", out, ap["wo"])
    return out, {"k": ck, "v": cv}


def _attn_decode_paged(x, ap, cfg: ModelConfig, pk, pv, layer, page_table,
                       pos, kv_kbits=None, write_mask=None,
                       paged_kernel=False):
    """One-token attention of layer ``layer`` against the *paged* KV
    pools.  x: (B, 1, D).

    ``pk``/``pv`` are the whole stacked pools ``(L, P, ps, K*hd)``
    (``paged_pool_specs``), carried through the layer loop and updated
    in place: the B new rows are one scatter at ``[layer, pidx, off]``,
    and the read takes layer ``layer`` straight from the stack, so no
    layer's pool is sliced out or copied.  ``page_table`` (B,
    max_pages) maps each lane's logical pages into the pool (see
    serve/paging.py).  ``pos`` is always a (B,) vector — the paged
    engine is ragged by construction.  The write lands at
    ``pool[layer, page_table[b, pos//ps], pos % ps]``; lanes outside
    ``write_mask`` (dead lanes waiting for admission) AND lanes whose
    position has outrun their page table (``pos // ps >= max_pages`` —
    an engine bug, but it must fail safe) are routed to the reserved
    trash page 0, so a live page can never be corrupted.  The read
    either gathers the lane's pages back into contiguous logical order
    (``gather_pages``, the oracle) and masks with the same per-sequence
    ``kv_valid_len`` as the contiguous path, or — with
    ``paged_kernel=True`` — walks the page table in place through the
    fused kernel (kernels/paged_attn), which DMAs the layer's pages
    from the stack and never materializes the gathered cache; both
    keep paged decode token-identical to the contiguous engine (locked
    by tests/test_serve_paged.py).  ``kv_kbits`` fake-quantizes the
    written slot at the same slot granularity as the contiguous path
    (one scale per (K, hd) row — the byte *accounting* is per page,
    the numerics per slot, so parity survives FRAC).  Returns
    (out, pk, pv).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    pos = jnp.asarray(pos)
    ppos = pos[:, None]                                    # (B, 1)
    q = apply_rope(q, ppos, cfg.rope_theta)
    k = apply_rope(k, ppos, cfg.rope_theta)
    with jax.named_scope(spans.KV_WRITE):
        if kv_kbits is not None:
            from repro.kernels.frac_pack import ops as fops

            k = fops.fake_quant_slots(k, kv_kbits, row_dims=2)
            v = fops.fake_quant_slots(v, kv_kbits, row_dims=2)
        ps = pk.shape[2]
        b = x.shape[0]
        mp = page_table.shape[1]
        cols_raw = pos // ps
        cols = jnp.clip(cols_raw, 0, mp - 1)
        pidx = page_table[jnp.arange(b), cols]             # (B,)
        # an out-of-table position must NOT clamp into the last
        # allocated page (that would overwrite a live slot in place) —
        # route it to the trash page exactly like a dead lane
        ok = (pidx > 0) & (cols_raw < mp)
        if write_mask is not None:
            ok = ok & write_mask
        pidx = jnp.where(ok, pidx, 0)                      # trash page
        off = pos % ps
        pk = pk.at[layer, pidx, off].set(k[:, 0].reshape(b, -1))
        pv = pv.at[layer, pidx, off].set(v[:, 0].reshape(b, -1))
    with jax.named_scope(spans.ATTN_READ):
        if paged_kernel:
            from repro.kernels.paged_attn import ops as pops

            out = pops.paged_attention(q[:, 0], pk, pv, page_table,
                                       pos, layer)[:, None]
        else:
            kv_shape = (b, -1, *k.shape[2:])               # (B, S, K, hd)
            kb = gather_pages(pk, page_table, layer).reshape(kv_shape)
            vb = gather_pages(pv, page_table, layer).reshape(kv_shape)
            out = attention(
                q, kb, vb, causal=False, kv_valid_len=pos + 1,
                q_positions=ppos
            )
    out = jnp.einsum("bshk,hkd->bsd", out, ap["wo"])
    return out, pk, pv


def _mlp(x, mp, cfg: ModelConfig):
    up = x @ mp["w_up"]
    if cfg.gated_mlp:
        h = activate(x @ mp["w_gate"], cfg.mlp_activation) * up
    else:
        h = activate(up, cfg.mlp_activation)
    return h @ mp["w_down"]


def _mix_mlp(x, bp, j, mlp_kind, cfg, decode=False):
    from repro.models.moe import moe_block, moe_block_decode

    if mlp_kind == "moe":
        if decode:
            # dropless dense-combine path: same weights read, no
            # capacity bookkeeping in the decode loop (see moe.py)
            return moe_block_decode(x, bp[f"moe_{j}"], cfg)
        return moe_block(x, bp[f"moe_{j}"], cfg)
    return _mlp(x, bp[f"mlp_{j}"], cfg)


# ---------------------------------------------------------------------------
# Period block: sequence (train/prefill) and decode forms
# ---------------------------------------------------------------------------


def block_seq(x, bp, cfg: ModelConfig, *, emit_cache: bool):
    """x: (B, S, D) through one period block; returns (x, cache|None)."""
    from repro.sharding.rules import shard_hint

    x = shard_hint(x, "batch", _seq_dim(cfg), "none")
    cache: dict[str, Any] = {}
    for j, (mixer, mlp_kind) in enumerate(sublayer_kinds(cfg)):
        h = rms_norm(x, bp[f"norm1_{j}"])
        if mixer == "attn":
            if emit_cache:
                mixed, c = _attn_seq(x=h, ap=bp[f"attn_{j}"], cfg=cfg, emit_cache=True)
                cache[f"k_{j}"], cache[f"v_{j}"] = c["k"], c["v"]
            else:
                mixed = _attn_seq(h, bp[f"attn_{j}"], cfg)
        else:
            mixed = mamba_mod.mamba_block(h, bp[f"mamba_{j}"], cfg)
            if emit_cache:
                st = mamba_prefill_state(h, bp[f"mamba_{j}"], cfg)
                cache[f"mconv_{j}"], cache[f"mssm_{j}"] = st["conv"], st["ssm"]
        if cfg.parallel_block:
            x = x + mixed + _mix_mlp(h, bp, j, mlp_kind, cfg)
        else:
            x = x + mixed
            h2 = rms_norm(x, bp[f"norm2_{j}"])
            x = x + _mix_mlp(h2, bp, j, mlp_kind, cfg)
    return x, (cache if emit_cache else None)


def mamba_prefill_state(h, mp, cfg: ModelConfig):
    """Recompute the mamba decode state after a prefill pass.

    Cheap relative to the block itself: re-runs in/conv projections and
    the scan to the final hidden state.
    """
    B, S, D = h.shape
    xz = h @ mp["in_proj"]
    x_in, _ = jnp.split(xz, 2, axis=-1)
    w = cfg.mamba_d_conv
    conv_win = x_in[:, S - (w - 1):, :].astype(jnp.bfloat16)
    x_c = jax.nn.silu(
        mamba_mod.causal_depthwise_conv(x_in, mp["conv_w"], mp["conv_b"])
    )
    dt, Bm, Cm = mamba_mod._ssm_inputs(x_c, mp, cfg)
    A = -jnp.exp(mp["A_log"])

    def body(hh, t):
        hh, _ = mamba_mod._ssm_step(hh, dt[:, t], Bm[:, t], Cm[:, t], x_c[:, t], A)
        return hh, None

    h0 = jnp.zeros((B, cfg.mamba_d_inner, cfg.mamba_d_state), jnp.float32)
    hN, _ = lax.scan(body, h0, jnp.arange(S))
    return {"conv": conv_win, "ssm": hN}


def block_decode(x, bp, bc, cfg: ModelConfig, pos, kv_kbits=None):
    """One token through one period block.  x: (B, 1, D)."""
    new_cache: dict[str, Any] = {}
    for j, (mixer, mlp_kind) in enumerate(sublayer_kinds(cfg)):
        h = rms_norm(x, bp[f"norm1_{j}"])
        if mixer == "attn":
            mixed, c = _attn_decode(
                h, bp[f"attn_{j}"], cfg, {"k": bc[f"k_{j}"], "v": bc[f"v_{j}"]},
                pos, kv_kbits,
            )
            new_cache[f"k_{j}"], new_cache[f"v_{j}"] = c["k"], c["v"]
        else:
            st = {"conv": bc[f"mconv_{j}"], "ssm": bc[f"mssm_{j}"]}
            out2d, st = mamba_mod.mamba_decode_step(h[:, 0], st, bp[f"mamba_{j}"], cfg)
            mixed = out2d[:, None, :]
            new_cache[f"mconv_{j}"], new_cache[f"mssm_{j}"] = st["conv"], st["ssm"]
        if cfg.parallel_block:
            x = x + mixed + _mix_mlp(h, bp, j, mlp_kind, cfg, decode=True)
        else:
            x = x + mixed
            h2 = rms_norm(x, bp[f"norm2_{j}"])
            x = x + _mix_mlp(h2, bp, j, mlp_kind, cfg, decode=True)
    return x, new_cache


def block_decode_paged(x, bp, pool, layer, cfg: ModelConfig, page_table,
                       pos, kv_kbits=None, write_mask=None,
                       paged_kernel=False):
    """One token through period block ``layer`` against the stacked
    paged pools, which come back with this block's rows written.
    Only pure-attention blocks page (model.supports_paged)."""
    pool = dict(pool)
    for j, (mixer, mlp_kind) in enumerate(sublayer_kinds(cfg)):
        assert mixer == "attn", "paged decode is attention-only"
        with jax.named_scope(spans.ATTN):
            h = rms_norm(x, bp[f"norm1_{j}"])
            mixed, pool[f"k_{j}"], pool[f"v_{j}"] = _attn_decode_paged(
                h, bp[f"attn_{j}"], cfg, pool[f"k_{j}"], pool[f"v_{j}"],
                layer, page_table, pos, kv_kbits, write_mask, paged_kernel,
            )
        with jax.named_scope(spans.MLP):
            if cfg.parallel_block:
                x = x + mixed + _mix_mlp(h, bp, j, mlp_kind, cfg,
                                         decode=True)
            else:
                x = x + mixed
                h2 = rms_norm(x, bp[f"norm2_{j}"])
                x = x + _mix_mlp(h2, bp, j, mlp_kind, cfg, decode=True)
    return x, pool


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------


def _seq_dim(cfg: ModelConfig) -> str:
    from repro.sharding.rules import active_layout

    return "seq" if active_layout(cfg).startswith("sp") else "none"


def _embed_in(cfg: ModelConfig, params, batch):
    from repro.sharding.rules import shard_hint

    if cfg.input_mode == "embeddings" and "embeds" in batch:
        x = batch["embeds"].astype(jnp.bfloat16)
    else:
        x = params["embed"][batch["tokens"]]
    return shard_hint(x, "batch", _seq_dim(cfg), "none")


def _lm_head(cfg: ModelConfig, params, x):
    from repro.sharding.rules import shard_hint

    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    sd = _seq_dim(cfg)
    return shard_hint(logits, "batch", sd, "none" if sd == "seq" else "vocab")


def _scan_blocks(cfg, params, x, fn):
    if cfg.remat == "full":
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    G = cfg.remat_group
    n_periods = cfg.num_layers // block_period(cfg)
    if G > 1 and n_periods % G == 0 and n_periods > G:
        # sqrt-L nested remat: only every G-th layer boundary is saved;
        # the backward recomputes one G-span at a time.
        grouped = jax.tree.map(
            lambda a: a.reshape(n_periods // G, G, *a.shape[1:]),
            params["layers"],
        )

        @jax.checkpoint
        def outer(x, gp):
            x, _ = lax.scan(fn, x, gp)
            return x, None

        x, _ = lax.scan(outer, x, grouped)
        return x, None
    return lax.scan(fn, x, params["layers"])


def forward(cfg: ModelConfig, params, batch) -> jax.Array:
    x = _embed_in(cfg, params, batch)

    def body(x, bp):
        x, _ = block_seq(x, bp, cfg, emit_cache=False)
        return x, None

    x, _ = _scan_blocks(cfg, params, x, body)
    x = rms_norm(x, params["final_norm"])
    return _lm_head(cfg, params, x)


def prefill(cfg: ModelConfig, params, batch, lengths=None):
    """Forward + cache emit.  ``lengths`` (B,) serves a ragged bucket:
    prompts are right-padded to the batch max, causal masking keeps
    every real token's activations bit-identical to an unpadded run,
    and the returned logits are each sequence's own last *real* token
    (index ``lengths - 1``).  Pad-slot cache rows are garbage — the
    ragged decode path masks them out via per-sequence valid lengths."""
    x = _embed_in(cfg, params, batch)

    def body(x, bp):
        return block_seq(x, bp, cfg, emit_cache=True)

    x, cache = _scan_blocks(cfg, params, x, body)
    if lengths is None:
        x = x[:, -1:]
    else:
        x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    x = rms_norm(x, params["final_norm"])
    return _lm_head(cfg, params, x), cache


def decode_step(cfg: ModelConfig, params, cache, tokens, pos, kv_kbits=None):
    """tokens: (B,) int32; pos: scalar int32 — or (B,) int32 for a
    ragged bucket (per-sequence absolute positions).  ``kv_kbits``
    FRAC-fake-quantizes the decode-written KV slot in place (see
    _attn_decode).  Returns (logits, cache)."""
    x = params["embed"][tokens][:, None, :]                 # (B, 1, D)

    def body(x, bp_bc):
        bp, bc = bp_bc
        return block_decode(x, bp, bc, cfg, pos, kv_kbits)

    if cfg.remat == "full":
        pass  # no grads in decode; remat irrelevant
    x, new_cache = lax.scan(body, x, (params["layers"], cache))
    x = rms_norm(x, params["final_norm"])
    return _lm_head(cfg, params, x)[:, 0], new_cache


def decode_step_paged(cfg: ModelConfig, params, pool, page_table, tokens,
                      pos, kv_kbits=None, write_mask=None,
                      paged_kernel=False):
    """tokens: (B,) int32; pos: (B,) int32 per-sequence positions;
    ``pool``: the paged KV pools, stacked over period blocks (leaves
    ``(n_periods, P, ps, K*hd)``, ``paged_pool_specs``);
    ``page_table``: (B, max_pages), one table for every layer (the
    whole stack grows in lockstep).  The pool is the layer scan's
    *carry*, next to the activations and the layer index, while the
    weights are its scanned operand: each layer writes its B new rows
    into the carried stack and reads its pages from it in place, so a
    step never slices, copies or rebuilds the pool.  ``paged_kernel``
    reads through the fused page-walk kernel instead of the gather
    oracle (see kernels/paged_attn).  Returns (logits, pool)."""
    x = params["embed"][tokens][:, None, :]                 # (B, 1, D)

    def body(carry, bp):
        x, pool, layer = carry
        x, pool = block_decode_paged(x, bp, pool, layer, cfg, page_table,
                                     pos, kv_kbits, write_mask,
                                     paged_kernel)
        return (x, pool, layer + 1), None

    (x, pool, _), _ = lax.scan(
        body, (x, pool, jnp.asarray(0, jnp.int32)), params["layers"])
    with jax.named_scope(spans.HEAD):
        x = rms_norm(x, params["final_norm"])
        return _lm_head(cfg, params, x)[:, 0], pool


def paged_pool_specs(cfg: ModelConfig, n_pages: int, page_size: int) -> dict:
    """LeafSpecs for the shared page pool (paged serve engine): one
    ``(n_periods, n_pages, page_size, K*hd)`` leaf per k/v sublayer.
    The last axis holds a slot's KV heads side by side, the paged
    kernel's own lane-dense layout (kernels/paged_attn), so the kernel
    DMAs pages straight from the stored pool; the prefill fill and the
    gather read reshape only their own (B, S, K, hd) operands.  The
    axis keeps the ``kv_heads`` label: the sharding rule splits it
    into contiguous column blocks, whole heads when K divides the
    model axis."""
    n_periods = cfg.num_layers // block_period(cfg)
    K, hd = cfg.num_kv_heads, cfg.head_dim
    block: dict[str, LeafSpec] = {}
    for j, (mixer, _) in enumerate(sublayer_kinds(cfg)):
        assert mixer == "attn", "paged pools are attention-only"
        for name in ("k", "v"):
            block[f"{name}_{j}"] = LeafSpec(
                (n_pages, page_size, K * hd),
                ("pages", "page_slots", "kv_heads"),
                init="zeros",
            )
    return jax.tree.map(
        lambda s: stacked(n_periods, s),
        block,
        is_leaf=lambda x: isinstance(x, LeafSpec),
    )


def init_cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """LeafSpecs for the decode cache (shapes + logical dims)."""
    n_periods = cfg.num_layers // block_period(cfg)
    Sc = min(seq_len, cfg.max_decode_window) if cfg.max_decode_window else seq_len
    K, hd = cfg.num_kv_heads, cfg.head_dim
    di, n, w = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    block: dict[str, LeafSpec] = {}
    for j, (mixer, _) in enumerate(sublayer_kinds(cfg)):
        if mixer == "attn":
            block[f"k_{j}"] = LeafSpec(
                (batch, Sc, K, hd), ("batch", "kv_seq", "kv_heads", "head_dim"),
                init="zeros",
            )
            block[f"v_{j}"] = LeafSpec(
                (batch, Sc, K, hd), ("batch", "kv_seq", "kv_heads", "head_dim"),
                init="zeros",
            )
        else:
            block[f"mconv_{j}"] = LeafSpec(
                (batch, w - 1, di), ("batch", "none", "mamba_inner"), init="zeros"
            )
            block[f"mssm_{j}"] = LeafSpec(
                (batch, di, n), ("batch", "mamba_inner", "none"),
                init="zeros", dtype=jnp.float32,
            )
    return jax.tree.map(
        lambda s: stacked(n_periods, s),
        block,
        is_leaf=lambda x: isinstance(x, LeafSpec),
    )
