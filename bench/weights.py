"""Seeded random weights, made on the device in one jitted call.

The layout, ``{path: (shape, std)}``, is the one the configuration's
reference reads (``layout(m)`` of ``bench/references/<name>.py``);
``check_layout`` compares the tree drawn from it with the shapes the
program asks for, so a program (or a reference) whose layout differs
fails loudly instead of being fed the wrong tree.  Weights are drawn
in bfloat16, the type they are served in: each leaf N(0, std^2), a
norm scale (std None) 1 + N(0, 0.1^2) (random, so a norm that is left
out shows).  Leaves are drawn in the order of their sorted paths, the
i-th from ``fold_in(key, i)``: a layout gives the same weights for a
seed whatever reference module holds it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


def make_params(layout: dict, seed: int, shardings=None):
    """The tree of ``layout`` in bfloat16 from one jitted call on the
    device; ``shardings`` (a tree of the program's layout) places each
    leaf where it is made, never whole on one device first."""
    names = sorted(layout)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, std = layout[name]
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
