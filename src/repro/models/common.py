"""Shared model machinery: param specs, norms, RoPE, attention.

Models are pure-functional pytrees.  Each model module defines
``param_specs(cfg)`` — a nested dict of :class:`LeafSpec` — from which
concrete init, abstract (ShapeDtypeStruct) init, and logical-axis trees
all derive, guaranteeing the three stay in sync.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Pytree = Any

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """shape + logical dim names + init for one parameter tensor.

    ``dims`` names each dimension from the sharding vocabulary
    (see sharding/rules.py): layers, embed, heads, kv_heads, head_dim,
    mlp, vocab, experts, mamba_inner, state, conv, lora, none.
    """

    shape: tuple[int, ...]
    dims: tuple[str, ...]
    init: str = "normal"            # normal | zeros | ones | <callable>
    scale: float = 0.02
    dtype: Any = jnp.bfloat16
    init_fn: Callable | None = None

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init_fn is not None:
            return self.init_fn(key, self.shape).astype(self.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        return (jax.random.normal(key, self.shape, jnp.float32) * self.scale).astype(
            self.dtype
        )


def is_leaf_spec(x) -> bool:
    return isinstance(x, LeafSpec)


def tree_init(specs: Pytree, rng: jax.Array) -> Pytree:
    """Materialize every LeafSpec with a distinct fold of ``rng``."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_leaf_spec)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(
        treedef, [s.materialize(k) for s, k in zip(leaves, keys)]
    )


def tree_abstract(specs: Pytree) -> Pytree:
    return jax.tree.map(lambda s: s.abstract(), specs, is_leaf=is_leaf_spec)


def tree_dims(specs: Pytree) -> Pytree:
    return jax.tree.map(lambda s: s.dims, specs, is_leaf=is_leaf_spec)


def count_params(specs: Pytree) -> int:
    return sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(specs, is_leaf=is_leaf_spec)
    )


def stacked(n: int, spec: LeafSpec) -> LeafSpec:
    """Prepend the scan ('layers') dimension."""
    return dataclasses.replace(
        spec, shape=(n, *spec.shape), dims=("layers", *spec.dims)
    )


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


def mlp(x: jax.Array, mp: dict, cfg) -> jax.Array:
    """The (optionally gated) MLP ``w_down(act(x @ w_gate) * x @ w_up)``
    of ``cfg.mlp_activation``, in x.dtype."""
    up = x @ mp["w_up"]
    if cfg.gated_mlp:
        h = activate(x @ mp["w_gate"], cfg.mlp_activation) * up
    else:
        h = activate(up, cfg.mlp_activation)
    return h @ mp["w_down"]


def sinusoidal_positions(seq: int, dim: int, offset=0) -> jax.Array:
    """Whisper-style sinusoidal embeddings; offset may be traced (decode)."""
    pos = jnp.arange(seq, dtype=jnp.float32) + offset
    inv = jnp.exp(
        -jnp.arange(0, dim, 2, dtype=jnp.float32) * (np.log(10000.0) / max(dim // 2 - 1, 1))
    )
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, nheads, head_dim); positions: (S,) possibly traced, or
    (B, S) when each batch row sits at its own absolute position (ragged
    serving buckets — see serve/engine.py)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                    # (hd/2,)
    # (S, hd/2) — or (B, S, hd/2) for per-sequence positions
    ang = positions.astype(jnp.float32)[..., :, None] * freqs[None, :]
    cos = jnp.cos(ang)[..., :, None, :]                    # (S, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, query-chunked)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,K,G,hd)  k: (B,Sk,K,hd) -> (B,K,G,Sq,Sk) fp32."""
    return jnp.einsum(
        "bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    )


def _gqa_out(p, v):
    """p: (B,K,G,Sq,Sk)  v: (B,Sk,K,hd) -> (B,Sq,K,G,hd)."""
    return jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)


def _softmax_attend(scores, mask, v):
    neg = jnp.asarray(NEG_INF if scores.dtype == jnp.float32 else -3e38,
                      scores.dtype)
    scores = jnp.where(mask, scores, neg)
    scores = scores - jax.lax.stop_gradient(scores.max(axis=-1, keepdims=True))
    probs = jnp.exp(scores)
    denom = probs.sum(axis=-1, keepdims=True, dtype=jnp.float32) + 1e-30
    probs = (probs / denom.astype(probs.dtype))
    return _gqa_out(probs, v)


def attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, K, hd)
    v: jax.Array,            # (B, Sk, K, hd)
    *,
    causal: bool = True,
    window: int = 0,         # 0 = full
    chunk: int = 0,          # 0 = unchunked
    kv_valid_len: jax.Array | None = None,  # decode: #valid cache slots
    q_positions: jax.Array | None = None,   # absolute position of each query
    scores_bf16: bool = False,  # halve the score transient (SP prefill)
) -> jax.Array:
    """Reference multi-mode attention (GQA + causal + sliding window).

    Query-chunked (flash-style restructuring without the kernel) when
    ``chunk`` divides Sq — keeps the (chunk, Sk) score block transient so
    32k prefill fits.  The Pallas SWA kernel replaces this on the hot
    path (kernels/swa_attention) — this is the oracle.

    ``kv_valid_len`` / ``q_positions`` may carry a leading batch dim
    ((B,) / (B, Sq)): each sequence then masks its own cache span — the
    ragged-bucket decode path, where per-sequence positions differ.
    Batched positions are only supported unchunked (decode has Sq = 1).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd) * (hd ** -0.5)

    kv_pos = jnp.arange(k.shape[1])
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    batched_mask = (q_positions.ndim > 1
                    or (kv_valid_len is not None and kv_valid_len.ndim > 0))

    def block(q_blk, q_pos_blk):
        if scores_bf16:
            # bf16 score buffer (f32-accumulated softmax denominator):
            # halves the dominant (B,K,G,Sq,Sk) transient in SP prefill
            scores = jnp.einsum("bqkgh,bskh->bkgqs", q_blk, k,
                                preferred_element_type=jnp.bfloat16)
        else:
            scores = _gqa_scores(q_blk, k)                   # (B,K,G,sq,Sk)
        # (sq, Sk) shared mask, or (B, sq, Sk) when positions/valid
        # lengths are per-sequence
        mask = jnp.ones((q_blk.shape[1], k.shape[1]), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos_blk[..., :, None]
        if window:
            mask &= kv_pos[None, :] > (q_pos_blk[..., :, None] - window)
        if kv_valid_len is not None:
            vl = jnp.asarray(kv_valid_len)
            if vl.ndim > 0:                                  # (B,) per-seq
                mask = mask & (kv_pos[None, None, :] < vl[:, None, None])
            else:
                mask &= (kv_pos < vl)[None, :]
        mask_b = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        return _softmax_attend(scores, mask_b, v)

    if batched_mask:
        assert not (chunk and Sq > chunk), \
            "per-sequence positions are decode-only (unchunked)"
    if chunk and Sq > chunk and Sq % chunk == 0:
        n = Sq // chunk
        # checkpoint the chunk: without it the backward saves per-chunk
        # fp32 scores+probs across all chunks (measured ~75 GiB/device
        # at 32L/4k); recomputing them costs ~+30% attention flops.
        blk = jax.checkpoint(block)

        def body(_, i):
            qs = lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
            ps = lax.dynamic_slice_in_dim(q_positions, i * chunk, chunk, axis=0)
            return None, blk(qs, ps)

        _, outs = lax.scan(body, None, jnp.arange(n))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, K, G, hd)
    else:
        out = block(qg, q_positions)
    return out.reshape(B, Sq, H, hd)


def gather_pages(pool: jax.Array, page_table: jax.Array,
                 layer) -> jax.Array:
    """Read layer ``layer`` of a stacked paged KV pool through a page
    table.

    ``pool``: (L, P, page_size, K*hd) stacked page pools (one gather
    indexes the layer and the pages together, so the layer's pool is
    never sliced out); ``page_table``: (B, max_pages) int32, each row
    the sequence's pages in logical order (unallocated entries are
    -1).  Returns (B, max_pages * page_size, K*hd): row ``r`` of lane
    ``b`` is logical position ``r`` — the contiguous cache layout once
    the caller splits the heads — so the per-sequence ``kv_valid_len``
    masks apply unchanged (positions ``>= pos+1`` are masked, which
    covers every row of an unallocated page).  Unallocated/trash entries
    (``page_table <= 0``) are replaced with exact zeros: the softmax
    mask gives them probability 0, but a zero probability times a NaN
    or inf value row would still be NaN in the weighted sum, so the
    "garbage but finite, always masked" contract requires sanitizing
    the values themselves, not just the scores (locked by the
    poisoned-trash-page test in tests/test_serve_paged.py).  The
    ``jnp.where`` (never a multiplicative mask — ``0 * nan`` is nan)
    is bit-transparent for finite garbage.
    """
    gathered = pool[layer, jnp.maximum(page_table, 0)]  # (B, MP, ps, K*hd)
    b, mp, ps = gathered.shape[:3]
    valid = (page_table > 0).reshape(
        b, mp, *([1] * (gathered.ndim - 2)))
    gathered = jnp.where(valid, gathered, jnp.zeros((), gathered.dtype))
    return gathered.reshape(b, mp * ps, *pool.shape[3:])


def windowed_prefill_attention(
    q, k, v, *, window: int, chunk: int, q_positions=None
) -> jax.Array:
    """Sub-quadratic SWA prefill: each query chunk sees only the
    (window + chunk) key slice ending at its own position.  Compute is
    O(S·(W+c)) instead of O(S²) — this is what makes mixtral's SWA path
    viable at 500k."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    assert Sq % chunk == 0, "pad queries to a chunk multiple"
    qg = q.reshape(B, Sq, K, G, hd) * (hd ** -0.5)
    span = window + chunk
    # left-pad keys/values so every slice is static-shaped
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    if q_positions is None:
        q_positions = jnp.arange(Sq)

    @jax.checkpoint
    def blk(q_blk, k_blk, v_blk, qpos, kpos):
        scores = _gqa_scores(q_blk, k_blk)
        mask = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window
        ) & (kpos >= 0)[None, :]
        return _softmax_attend(scores, mask[None, None, None], v_blk)

    def body(_, i):
        q_blk = lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
        k_blk = lax.dynamic_slice_in_dim(kp, i * chunk, span, axis=1)
        v_blk = lax.dynamic_slice_in_dim(vp, i * chunk, span, axis=1)
        qpos = lax.dynamic_slice_in_dim(q_positions, i * chunk, chunk, axis=0)
        kpos = i * chunk - window + jnp.arange(span)
        return None, blk(q_blk, k_blk, v_blk, qpos, kpos)

    _, outs = lax.scan(body, None, jnp.arange(Sq // chunk))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def causal_depthwise_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B,S,C), w: (C,width), b: (C,) — causal depthwise conv."""
    width = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(width):
        out = out + xp[:, j : j + x.shape[1], :].astype(jnp.float32) * w[:, j].astype(
            jnp.float32
        )
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token CE; stable under a vocab-sharded last axis.

    Uses a one-hot contraction rather than take_along_axis: the gather
    form forces GSPMD to all-gather the (B,S,V) logits over the model
    axis (measured: +22 GiB/device on llama3-3b), while the contraction
    stays vocab-sharded and lowers the reductions to psums.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    # bf16 one-hot is exact (values 0/1) and halves the (B,S,V) temp
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.bfloat16)
    gold = jnp.einsum("...v,...v->...", lf, onehot)
    return jnp.mean(lse - gold)


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
