"""Plain float32 reference of Step-3's text decoder (``step3.ep6``), its
control, and the weight layout it reads.

Written from the configuration alone (``m``, the config's ``model``
block) in straightforward ``jax.numpy``; it imports nothing of the
program.  The helpers it shares with the dense reference (RoPE on
split halves, the KV tier's fake-quant, fp8 rounding, causal attention
over raw prompt rows and stored decode rows, the head in vocabulary
blocks) come from ``dense.py`` beside it.

The block, pre-norm RMS (eps ``norm_eps``) with sequential residuals:

- attention (MFA): ``q = wq_b(RMSNorm(wq_a(h)))``, 64 heads of 256
  against ``num_kv_heads`` (1) shared K and V heads of 256, RoPE over
  the whole head, then ``wo``;
- the expert layer: a float32 softmax router over all ``num_experts``
  (48) outputs, top-k (3), gates renormalized only where ``moe_renorm``
  says; the routed part comes from the held experts alone,
  ``[experts_held_start, experts_held_start + experts_held)``, each a
  gated-SiLU MLP of ``d_ff``; a shared gated-SiLU expert of
  ``shared_expert_ff`` is added on every token.

The deployment's share is the configuration's: the vocabulary is the
slice ``vocab_size`` and what experts held on other chips add is left
out, as the program leaves it out.  Each held expert runs over every
token and is weighted by the token's gate for it (zero where the token
did not choose it), one expert per jitted call, so float32 never holds
more than one expert's weights beside the served bf16 ones.

``gaps`` is ``dense.gaps`` for this block: a teacher-forced pass over
prompt and served tokens, each served token's logit below the
reference's best; with ``control=True`` the same pass one precision
step down (fp8 e4m3 weights and matmul inputs, router included, and a
4-bit KV tier), read at the token the control ranks first.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.dense import (F32, HI, PAD, _act, _attend, _embed_fn,
                                    _logit_blocks, _pick, fp8, kv_quant, rope)

ROUTER_LOGIT_STD = 2.0   # router logits' std at a unit-RMS input


def _check_block(m: dict) -> None:
    want = {"gated_mlp": True, "parallel_block": False, "tie_embeddings": False,
            "moe_interleave": 1, "mlp_activation": "silu"}
    off = {k: m.get(k) for k, v in want.items() if m.get(k) != v}
    if off or not (m["q_lora_rank"] and m["shared_expert_ff"]):
        raise ValueError(f"step3 reference: block {off or m} not handled")


def layout(m: dict) -> dict:
    """{path: (shape, std)} of the served stack; std None for a norm
    scale.  Matrices N(0, 1/fan_in), the three that write into the
    residual stream (``wo`` and both experts' ``w_down``) a further
    1/sqrt(2L) down, the embedding N(0, 1) (see ``dense.layout`` for
    why the scale matters to the check).  The router's logits have std
    ``ROUTER_LOGIT_STD`` at a unit-RMS input: peaked enough that the
    top three experts carry a sizable share of the softmax, so the
    routed part moves the logits and not only the shared expert."""
    _check_block(m)
    L, D, H, K = m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, r, F, Fs = m["head_dim"], m["q_lora_rank"], m["d_ff"], m["shared_expert_ff"]
    E, Eh, V = m["num_experts"], m["experts_held"] or m["num_experts"], m["vocab_size"]
    into_residual = (2 * L) ** -0.5
    return {
        "embed": ((V, D), 1.0),
        "lm_head": ((D, V), D ** -0.5),
        "final_norm": ((D,), None),
        "layers/norm1_0": ((L, D), None),
        "layers/norm2_0": ((L, D), None),
        "layers/attn_0/wq_a": ((L, D, r), D ** -0.5),
        "layers/attn_0/q_norm": ((L, r), None),
        "layers/attn_0/wq_b": ((L, r, H, hd), r ** -0.5),
        "layers/attn_0/wk": ((L, D, K, hd), D ** -0.5),
        "layers/attn_0/wv": ((L, D, K, hd), D ** -0.5),
        "layers/attn_0/wo": ((L, H, hd, D), (H * hd) ** -0.5 * into_residual),
        "layers/moe_0/router": ((L, D, E), ROUTER_LOGIT_STD * D ** -0.5),
        "layers/moe_0/w_up": ((L, Eh, D, F), D ** -0.5),
        "layers/moe_0/w_gate": ((L, Eh, D, F), D ** -0.5),
        "layers/moe_0/w_down": ((L, Eh, F, D), F ** -0.5 * into_residual),
        "layers/moe_0/shared/w_up": ((L, D, Fs), D ** -0.5),
        "layers/moe_0/shared/w_gate": ((L, D, Fs), D ** -0.5),
        "layers/moe_0/shared/w_down": ((L, Fs, D), Fs ** -0.5 * into_residual),
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ops(lowp: bool):
    """(weight, matmul) at the configuration's precision or the
    control's: fp8 weights per output column, inputs per row."""
    def w(a, shape):
        a = a.astype(F32).reshape(shape)
        return fp8(a, 0) if lowp else a

    def mm(a, b):
        return jnp.matmul(fp8(a, -1) if lowp else a, b, precision=HI)
    return w, mm


def _mlp(h, up, gate, down, mm):
    return mm(_act(mm(h, gate), "silu") * mm(h, up), down)


@lru_cache(maxsize=None)
def _attn_fn(mt: tuple, kv_bits: int, lowp: bool):
    """Layer ``l``'s attention sublayer, its residual and the expert
    layer's input: x -> (x + attn, RMSNorm(x + attn))."""
    m = dict(mt)
    D, H, K, hd, r = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"], m["q_lora_rank"])
    eps = m["norm_eps"]
    w, mm = _ops(lowp)

    def attn(x, layers, l, P):
        lw = jax.tree.map(lambda a: a[l], {k: layers[k] for k in
                                           ("attn_0", "norm1_0", "norm2_0")})
        T = x.shape[0]
        pos = jnp.arange(T)
        at = lw["attn_0"]
        h = rms_norm(x, lw["norm1_0"].astype(F32), eps)
        qa = rms_norm(mm(h, w(at["wq_a"], (D, r))), at["q_norm"].astype(F32), eps)
        q = mm(qa, w(at["wq_b"], (r, H * hd))).reshape(T, H, hd)
        k = mm(h, w(at["wk"], (D, K * hd))).reshape(T, K, hd)
        v = mm(h, w(at["wv"], (D, K * hd))).reshape(T, K, hd)
        q = rope(q, pos, m["rope_theta"]) * hd ** -0.5
        k = rope(k, pos, m["rope_theta"])
        o = _attend(q.reshape(T, K, H // K, hd), k, v, kv_quant(k, kv_bits),
                    kv_quant(v, kv_bits), P)
        x = x + mm(o.reshape(T, H * hd), w(at["wo"], (H * hd, D)))
        return x, rms_norm(x, lw["norm2_0"].astype(F32), eps)

    return jax.jit(attn)


@lru_cache(maxsize=None)
def _route_fn(mt: tuple, lowp: bool):
    """h (T, D) -> (T, Eh) each token's gate for each held expert (zero
    where it did not choose the expert), and the shared expert's
    output."""
    m = dict(mt)
    D, k = m["d_model"], m["experts_per_token"]
    Eh = m["experts_held"] or m["num_experts"]
    w, mm = _ops(lowp)

    def route(h, moe, l):
        probs = jax.nn.softmax(mm(h, w(moe["router"][l], (D, -1))), axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        if m["moe_renorm"]:
            gate = gate / gate.sum(-1, keepdims=True)
        local = idx - m["experts_held_start"]
        held = jnp.einsum("tk,tke->te", gate, jax.nn.one_hot(local, Eh, dtype=F32),
                          precision=HI)
        sh = moe["shared"]
        shared = _mlp(h, w(sh["w_up"][l], (D, -1)), w(sh["w_gate"][l], (D, -1)),
                      w(sh["w_down"][l], (-1, D)), mm)
        return held, shared

    return jax.jit(route)


@lru_cache(maxsize=None)
def _expert_fn(mt: tuple, lowp: bool):
    """Held expert ``e`` of layer ``l`` over every token, times each
    token's gate for it."""
    D = dict(mt)["d_model"]
    w, mm = _ops(lowp)

    def expert(h, g, moe, l, e):
        y = _mlp(h, w(moe["w_up"][l, e], (D, -1)), w(moe["w_gate"][l, e], (D, -1)),
                 w(moe["w_down"][l, e], (-1, D)), mm)
        return g[:, e, None] * y

    return jax.jit(expert)


def moe_layer(h, moe, m: dict, l: int, lowp: bool = False):
    """The expert layer of layer ``l`` over h (T, D): the held experts'
    routed part plus the shared expert, float32."""
    mt = tuple(sorted(m.items()))
    g, y = _route_fn(mt, lowp)(h, moe, l)
    expert = _expert_fn(mt, lowp)
    for e in range(m["experts_held"] or m["num_experts"]):
        y = y + expert(h, g, moe, l, e)
    return y


def _hidden(params, m, tokens, P, kv_bits, lowp):
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    x = _embed_fn(lowp)(params["embed"], jnp.asarray(tok))
    attn = _attn_fn(tuple(sorted(m.items())), kv_bits, lowp)
    layers = params["layers"]
    for l in range(m["num_layers"]):
        x, h = attn(x, layers, l, P)
        x = x + moe_layer(h, layers["moe_0"], m, l, lowp)
    return x


@jax.jit
def _final(x, w, rows, eps):
    return rms_norm(x[rows], w.astype(F32), eps)


def gaps(params, m: dict, prompt, served, kv_bits: int,
         control: bool = False) -> dict:
    """Teacher-forced reference over ``prompt`` + ``served``.

    Returns {"program": (n,) gap of each served token below the
    reference's best logit} and, with ``control``, {"control": (n,)
    gap of the token the control ranks first}."""
    _check_block(m)
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    P, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = jnp.arange(P - 1, P - 1 + n)
    eps = m["norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = _final(_hidden(params, m, tokens, P, kv_bits, False),
                   params["final_norm"], rows, eps)
        best = jnp.full((n,), -jnp.inf, F32)
        blocks = []
        for s, c, lg in _logit_blocks(params, m, h, False):
            best = jnp.maximum(best, lg.max(-1))
            blocks.append((s, c, lg))
        out = {"program": np.asarray(best - _pick(blocks, jnp.asarray(served)))}
        if control:
            hc = _final(_hidden(params, m, tokens, P, max(kv_bits // 2, 1), True),
                        params["final_norm"], rows, eps)
            cbest = jnp.full((n,), -jnp.inf, F32)
            cidx = jnp.zeros((n,), jnp.int32)
            for s, c, lg in _logit_blocks(params, m, hc, True):
                mx, ix = lg.max(-1), lg.argmax(-1).astype(jnp.int32) + s
                take = mx > cbest
                cbest = jnp.where(take, mx, cbest)
                cidx = jnp.where(take, ix, cidx)
            out["control"] = np.asarray(best - _pick(blocks, cidx))
    return out
