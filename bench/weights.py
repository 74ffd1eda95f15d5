"""Seeded random weights, made on the device in one jitted call.

The layout is the one the engine serves a dense stack in (period-one
blocks stacked over layers); ``check_layout`` compares it with the
shapes the program asks for, so a program that changes its layout
fails loudly instead of being fed the wrong tree.  Weights are drawn
in bfloat16, the type they are served in: each matrix N(0, 1/fan_in),
the two that write into the residual stream (``wo``, ``w_down``) a
further 1/sqrt(2L) down, the embedding N(0, 1), norm scales 1 + N(0,
0.1^2) (random, so a norm that is left out shows).

The scale matters to the correctness check.  With every matrix at one
small std the stack's branches swamp the embedding, and a squared-ReLU
MLP (whose outputs have a positive mean) maps every token to nearly the
same vector: greedy decoding then emits one token whatever the prompt,
so neither a fault in the KV cache nor a lower precision shows.  Here
the token and its context keep a say in every logit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def layout(m: dict) -> dict:
    """{path: (shape, std)} of a dense decoder; std None for a norm scale."""
    L, D, H, K = m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, F, V = m["head_dim"], m["d_ff"], m["vocab_size"]
    into_residual = (2 * L) ** -0.5
    out = {
        "embed": ((V, D), 1.0),
        "layers/attn_0/wq": ((L, D, H, hd), D ** -0.5),
        "layers/attn_0/wk": ((L, D, K, hd), D ** -0.5),
        "layers/attn_0/wv": ((L, D, K, hd), D ** -0.5),
        "layers/attn_0/wo": ((L, H, hd, D), (H * hd) ** -0.5 * into_residual),
        "layers/norm1_0": ((L, D), None),
        "layers/mlp_0/w_up": ((L, D, F), D ** -0.5),
        "layers/mlp_0/w_down": ((L, F, D), F ** -0.5 * into_residual),
        "final_norm": ((D,), None),
    }
    if m["gated_mlp"]:
        out["layers/mlp_0/w_gate"] = ((L, D, F), D ** -0.5)
    if not m["parallel_block"]:
        out["layers/norm2_0"] = ((L, D), None)
    if not m["tie_embeddings"]:
        out["lm_head"] = ((D, V), D ** -0.5)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def flat(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, p + "/"))
        else:
            out[p] = v
    return out


def seed_key(seed: int) -> jax.Array:
    """A key that uses all 64 bits of ``seed`` (PRNGKey keeps 32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_params(m: dict, seed: int, shardings=None):
    """The whole tree in bfloat16 from one jitted call on the device;
    ``shardings`` (a tree of the program's layout) places each leaf
    where it is made, never whole on one device first."""
    spec = layout(m)
    names = sorted(spec)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, std = spec[name]
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.bfloat16)
            if std is None:
                out[name] = (1.0 + 0.1 * z).astype(jnp.bfloat16)
            else:
                out[name] = (std * z).astype(jnp.bfloat16)
        return _nest(out)

    return jax.jit(draw, out_shardings=shardings)(seed_key(seed))


def check_layout(ours, program_abstract) -> None:
    """Raise unless both trees have the same paths, shapes and dtypes."""
    a = {k: (tuple(v.shape), np.dtype(v.dtype)) for k, v in flat(ours).items()}
    b = {k: (tuple(v.shape), np.dtype(v.dtype))
         for k, v in flat(program_abstract).items()}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"weight layout differs from the program's: {diff}")
