"""Mixture-of-Experts blocks: the dropless serving layer and training's
GShard-style grouped dispatch/combine.

Both route every token over all ``num_experts`` router outputs (fp32
softmax, top-k, gates renormalized where ``cfg.moe_renorm`` says) and
compute the part of the result that this chip's held experts give:
``[experts_held_start, experts_held_start + n_held_experts)`` of them
(all of them unless the config says otherwise, see configs/base.py).
What the absent experts add lives on other chips; nothing here stands
in for it.  A shared expert (``cfg.shared_expert_ff``) runs on every
token and is added to the routed part.

``moe_layer`` serves prefill and decode.  Prefill's many tokens take
the sorted form: the (token, choice) pairs are sorted by held expert
and fed to one grouped matmul (``lax.ragged_dot``) per projection over
the bf16 expert weights, fp32 accumulation; pairs routed to an expert
held elsewhere, and a ragged bucket's pad slots, sort after every held
group and come out as zeros, so a prefill's expert work follows its
real tokens alone (pad slots, all one token id, would otherwise route
alike and pile onto the same experts).  A
decode step's one token per lane takes the dense form: every held
expert runs on every token as one batched matmul per projection, and
each token's gate (zero for an expert it did not choose) weights the
result.  At a handful of tokens the dense form's extra compute is
negligible and it reads each held expert once, in place in the layer
stack; the grouped matmul is a custom call on a TPU, which needs its
weights as a buffer of their own, so inside the decode loop's layer
scan it would copy the layer's held experts out of the stack every
step before reading them.  Both forms are dropless and per-token:
nothing is shared between tokens, so a lane's result never depends on
its bucket neighbours (the serving engine's ragged-parity invariant).

``moe_block`` is training's path: token-choice top-k with per-group
expert capacity, whose grouped einsums lower to clean all-to-alls
under SPMD when the expert dimension is sharded (EP over the 'model'
axis) — see DESIGN.md.  Group size is kept small (<= 512 tokens) so
the dispatch/combine einsums stay <5% of expert FLOPs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import spans
from repro.configs.base import ModelConfig
from repro.models.common import LeafSpec, activate, mlp

HI = jax.lax.Precision.HIGHEST


def moe_param_specs(cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_held_experts
    specs = {
        "router": LeafSpec((D, cfg.num_experts), ("embed", "none")),
        "w_up": LeafSpec((E, D, F), ("experts", "embed", "mlp")),
        "w_down": LeafSpec((E, F, D), ("experts", "mlp", "embed")),
    }
    if cfg.gated_mlp:
        specs["w_gate"] = LeafSpec((E, D, F), ("experts", "embed", "mlp"))
    if cfg.shared_expert_ff:
        Fs = cfg.shared_expert_ff
        specs["shared"] = {
            "w_up": LeafSpec((D, Fs), ("embed", "mlp")),
            "w_down": LeafSpec((Fs, D), ("mlp", "embed")),
        }
        if cfg.gated_mlp:
            specs["shared"]["w_gate"] = LeafSpec((D, Fs), ("embed", "mlp"))
    return specs


def route(x: jax.Array, router: jax.Array, cfg: ModelConfig):
    """x (N, D) -> gates (N, k) fp32 and expert ids (N, k) over all
    ``num_experts``: an fp32 softmax of the router's logits, its top-k,
    renormalized only where ``cfg.moe_renorm``."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=HI)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                              cfg.experts_per_token)
    if cfg.moe_renorm:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
    return gate, idx


def held_reads(idx: jax.Array, live: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Held experts that at least one ``live`` token routed to: idx
    (N, k) expert ids, live (N,) bool -> int32 scalar."""
    local = idx - cfg.experts_held_start
    hit = jax.nn.one_hot(jnp.where(live[:, None], local, -1),
                         cfg.n_held_experts, dtype=jnp.int32)  # (N, k, E)
    return hit.max(axis=(0, 1)).sum()


def routed_experts(x: jax.Array, p: dict, cfg: ModelConfig, gate, idx,
                   live=None):
    """This chip's part of the routed result: x (N, D) -> (N, D) fp32.

    The N·k (token, choice) pairs sort by held expert (pairs held
    elsewhere, and every pair of a token not ``live``, last: their
    part is zero), one grouped matmul per projection runs over the
    sorted rows, and each pair's output, scaled by its gate, returns to
    its token by the inverse permutation and sums over the k choices in
    a fixed order."""
    N, D = x.shape
    k, E = cfg.experts_per_token, cfg.n_held_experts
    local = (idx - cfg.experts_held_start).reshape(-1)            # (N·k,)
    mine = (local >= 0) & (local < E)
    if live is not None:
        mine = mine & jnp.repeat(live, k)
    key = jnp.where(mine, local, E)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, E, dtype=jnp.int32), axis=0)
    xs = x[order // k]                                            # (N·k, D)

    def gmm(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    up = gmm(xs, p["w_up"])
    if cfg.gated_mlp:
        h = activate(gmm(xs, p["w_gate"]), cfg.mlp_activation) * up
    else:
        h = activate(up, cfg.mlp_activation)
    ys = gmm(h.astype(x.dtype), p["w_down"])                      # (N·k, D)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
    y = jnp.where(mine[:, None], ys[inv], 0.0).reshape(N, k, D)
    return jnp.einsum("nkd,nk->nd", y, gate, precision=HI)


def dense_experts(x: jax.Array, p: dict, cfg: ModelConfig, gate, idx):
    """``routed_experts`` for a few tokens: every held expert on every
    token, x (N, D) -> (N, D) fp32, each expert's output weighted by the
    token's gate for it (zero where the token did not choose it)."""
    local = idx - cfg.experts_held_start                          # (N, k)
    w = jnp.einsum("nk,nke->ne", gate,
                   jax.nn.one_hot(local, cfg.n_held_experts, dtype=jnp.float32),
                   precision=HI)                                  # (N, E)

    def bmm(a, b, spec):
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    up = bmm(x, p["w_up"], "nd,edf->enf")
    if cfg.gated_mlp:
        h = activate(bmm(x, p["w_gate"], "nd,edf->enf"), cfg.mlp_activation) * up
    else:
        h = activate(up, cfg.mlp_activation)
    ys = bmm(h.astype(x.dtype), p["w_down"], "enf,efd->end")     # (E, N, D)
    return jnp.einsum("end,ne->nd", ys, w, precision=HI)


def moe_layer(x: jax.Array, p: dict, cfg: ModelConfig, live=None,
              decode: bool = False):
    """Dropless MoE for serving: x (..., D) -> (y in x.dtype, reads).

    ``decode`` (one token per lane) takes the dense form, prefill the
    sorted one (see the module's docstring).  ``live`` (one bool per
    token) marks the tokens that serve a request: decode lanes, or a
    prefill's real tokens (the sorted form leaves the others' routed
    part out); ``reads`` counts the held experts at least one of them
    routed to (0 without ``live``)."""
    xt = x.reshape(-1, x.shape[-1])
    with jax.named_scope(spans.MOE_ROUTE):
        gate, idx = route(xt, p["router"], cfg)
        reads = (jnp.int32(0) if live is None
                 else held_reads(idx, live.reshape(-1), cfg))
    with jax.named_scope(spans.MOE_EXPERTS):
        if decode:
            y = dense_experts(xt, p, cfg, gate, idx)
        else:
            y = routed_experts(xt, p, cfg, gate, idx,
                               None if live is None else live.reshape(-1))
    if "shared" in p:
        with jax.named_scope(spans.MOE_SHARED):
            y = y + mlp(xt, p["shared"], cfg).astype(jnp.float32)
    return y.astype(x.dtype).reshape(x.shape), reads


def _capacity(group_tokens: int, k: int, num_experts: int, cf: float) -> int:
    c = int(group_tokens * k * cf / num_experts) + 1
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_block(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Training's grouped routing with per-expert capacity.
    x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    E, k = cfg.n_held_experts, cfg.experts_per_token
    group = min(cfg.moe_group, B * S)
    n_groups = (B * S) // group
    xt = x.reshape(n_groups, group, D)

    # --- routing (fp32) ---------------------------------------------------
    gate, idx = route(xt.reshape(-1, D), p["router"], cfg)
    gate = gate.reshape(n_groups, group, k)
    idx = idx.reshape(n_groups, group, k) - cfg.experts_held_start

    # --- capacity + positions (exact integer bookkeeping) -------------------
    # per-expert capacity of the whole router; experts held elsewhere
    # one-hot to an all-zero row and take no slot here
    C = _capacity(group, k, cfg.num_experts, cfg.capacity_factor)
    sel = jax.nn.one_hot(idx, E, dtype=jnp.int32)           # (g, s, k, E)
    selflat = sel.reshape(n_groups, group * k, E)
    pos = jnp.cumsum(selflat, axis=1) - selflat             # slot within expert
    keep = (pos < C) & (selflat > 0)                        # (g, s*k, E)
    slot = jax.nn.one_hot(pos, C, dtype=jnp.bfloat16)       # (g, s*k, E, C)
    # (g, s, k, E, C): 1 where (token, choice) landed a capacity slot
    keep_slot = (keep[..., None].astype(jnp.bfloat16) * slot).reshape(
        n_groups, group, k, E, C
    )
    dispatch = keep_slot.sum(axis=2)                        # (g, s, E, C)
    # combine weights: gate value of the (token, choice) that landed a slot
    combine = jnp.einsum(
        "gsk,gskec->gsec", gate.astype(jnp.bfloat16), keep_slot
    )

    # --- dispatch -> expert compute -> combine ------------------------------
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xt)
    up = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    if cfg.gated_mlp:
        gatep = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
        h = activate(gatep, cfg.mlp_activation) * up
    else:
        h = activate(up, cfg.mlp_activation)
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    out = out.reshape(B, S, D)
    if "shared" in p:
        out = out + mlp(x, p["shared"], cfg)
    return out
