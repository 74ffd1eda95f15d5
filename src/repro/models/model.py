"""Unified model API: family dispatch + init/abstract/axes + loss.

Every caller (train loop, serve engine, dry-run, tests) goes through
this module, so the three views of a model — concrete params, abstract
params, logical sharding axes — are guaranteed consistent.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import encdec, rwkv, transformer
from repro.models.common import (
    LeafSpec,
    cross_entropy,
    is_leaf_spec,
    tree_abstract,
    tree_dims,
    tree_init,
)

Pytree = Any


def _mod(cfg: ModelConfig):
    if cfg.family == "ssm":
        return rwkv
    if cfg.family == "audio":
        return encdec
    return transformer  # dense | moe | vlm | hybrid


# -- params -----------------------------------------------------------------


def param_specs(cfg: ModelConfig) -> Pytree:
    return _mod(cfg).param_specs(cfg)


def init_params(cfg: ModelConfig, rng: jax.Array) -> Pytree:
    return tree_init(param_specs(cfg), rng)


def init_params_jit(cfg: ModelConfig, rng: jax.Array,
                    shardings: Pytree | None = None) -> Pytree:
    """``init_params`` as one jitted program: each leaf is drawn and
    cast inside it, so no fp32 copy of a whole leaf is ever resident,
    and ``shardings`` (a NamedSharding tree, e.g.
    ``rules.param_shardings``) places every leaf where it is created —
    never whole on one device first.  Same distribution as
    ``init_params``, not the same bits."""
    specs = param_specs(cfg)
    return jax.jit(lambda key: tree_init(specs, key),
                   out_shardings=shardings)(rng)


def abstract_params(cfg: ModelConfig) -> Pytree:
    return tree_abstract(param_specs(cfg))


def param_axes(cfg: ModelConfig) -> Pytree:
    return tree_dims(param_specs(cfg))


def count_params(cfg: ModelConfig) -> int:
    return sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(param_specs(cfg), is_leaf=is_leaf_spec)
    )


def count_active_params(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top-k of E experts) — the N in
    MODEL_FLOPS = 6·N_active·D."""
    total = 0
    for path, s in jax.tree.flatten_with_path(
        param_specs(cfg), is_leaf=is_leaf_spec
    )[0]:
        n = int(np.prod(s.shape))
        if "experts" in s.dims and cfg.num_experts:
            n = n * cfg.experts_per_token // cfg.num_experts
        total += n
    return total


# -- entry points --------------------------------------------------------------


def forward(cfg: ModelConfig, params, batch) -> jax.Array:
    return _mod(cfg).forward(cfg, params, batch)


def loss_fn(cfg: ModelConfig, params, batch) -> jax.Array:
    """Mean next-token cross entropy (labels shifted here)."""
    logits = forward(cfg, params, batch)
    return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])


def prefill(cfg: ModelConfig, params, batch, lengths=None):
    """Forward + cache emit.  ``lengths`` (B,) int32 serves a ragged
    right-padded bucket (mixed prompt lengths sharing one prefill); the
    returned logits are then each sequence's own last real token.  Only
    valid when :func:`supports_ragged`."""
    return _mod(cfg).prefill(cfg, params, batch, lengths=lengths)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos, *,
                kv_kbits: int | None = None):
    """One decode step.  ``pos`` is a scalar, or (B,) per-sequence
    positions for a ragged bucket (attention families).  ``kv_kbits``
    fake-quantizes decode-written KV slots through the FRAC pipeline as
    they are produced (no-op for state-space caches, which are rewritten
    in place rather than appended)."""
    return _mod(cfg).decode_step(cfg, params, cache, tokens, pos, kv_kbits)


def decode_step_paged(cfg: ModelConfig, params, pool, page_table, tokens,
                      pos, *, kv_kbits: int | None = None, write_mask=None,
                      paged_kernel: bool = False):
    """One decode step against a paged KV pool (see serve/paging.py).
    ``pos`` is always (B,); ``write_mask`` (B,) bool routes dead lanes'
    cache writes to the trash page.  ``paged_kernel`` swaps the gather
    oracle for the fused page-walk read (kernels/paged_attn).  Only
    valid when :func:`supports_paged`."""
    assert supports_paged(cfg), f"{cfg.name}: family does not page"
    return transformer.decode_step_paged(cfg, params, pool, page_table,
                                         tokens, pos, kv_kbits, write_mask,
                                         paged_kernel)


def supports_paged(cfg: ModelConfig) -> bool:
    """Whether the family serves through the paged KV pool.

    True for the attention families that already serve ragged buckets
    with a full-length cache, dense and MoE — their decode appends one
    KV row per step at a per-sequence position, which maps 1:1 onto
    page-table writes.  False for state-space families (rwkv: O(1)
    state, nothing to page — the engine falls back to the contiguous
    path), rolling (SWA) windows (the rolling slot write crosses page
    boundaries mid-stream), and the hybrid/audio families that cannot
    share a ragged prefill (paged admission pre-stages requests through
    one ragged prefill)."""
    return supports_ragged(cfg) and cfg.family != "ssm"


def paged_pool_specs(cfg: ModelConfig, n_pages: int, page_size: int):
    """LeafSpecs for the shared paged KV pool (shapes + logical dims)."""
    assert supports_paged(cfg), f"{cfg.name}: family does not page"
    return transformer.paged_pool_specs(cfg, n_pages, page_size)


def supports_ragged(cfg: ModelConfig) -> bool:
    """Whether mixed-length (right-padded) buckets serve with outputs
    bit-identical to solo serving.

    True for pure-attention stacks with a full-length cache, dense or
    MoE (per-sequence valid masks hide pad slots; serving's expert
    layer is dropless and per-token, moe.moe_layer, so no lane's
    routing depends on another's) and for rwkv (prefill freezes each
    lane's state at its own length).  False for rolling (SWA) caches —
    the window emit is slot-aligned across the batch — and for
    hybrid/audio, whose mamba / encoder state emit has no per-lane
    length masking."""
    if cfg.family == "ssm":
        return True
    if cfg.family in ("audio", "hybrid"):
        return False
    return cfg.max_decode_window == 0


# -- caches ----------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Pytree:
    return _mod(cfg).init_cache_specs(cfg, batch, seq_len)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Pytree:
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch, seq_len),
        is_leaf=is_leaf_spec,
    )


def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Pytree:
    return tree_abstract(cache_specs(cfg, batch, seq_len))


def cache_axes(cfg: ModelConfig, batch: int, seq_len: int) -> Pytree:
    return tree_dims(cache_specs(cfg, batch, seq_len))
