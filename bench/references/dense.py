"""Plain float32 reference of a dense configuration, its control, and
the weight layout it reads.

A configuration names its reference (``"reference": "dense"``); the
harness loads ``bench/references/<name>.py`` and calls its ``layout``
(the weight tree ``bench/weights.py`` draws) and ``gaps`` (the check).

Written from the configuration alone (``m``, a config's ``model``
block) in straightforward ``jax.numpy``; it imports nothing of the
program.  It follows the block the configuration runs (pre-norm RMS,
RoPE on split halves, GQA, squared-ReLU or gated SiLU MLP, optional
parallel attention+MLP residual), and the KV tier the configuration
states: every key and value row (one position, all KV heads) stored
in the cache is fake-quantized with one absmax scale to ``2^k - 1``
levels.  The prompt's own attention reads its unquantized keys and
values (they are quantized as they are stored); every decode position
reads the stored, quantized rows.

``gaps`` runs one teacher-forced pass over a prompt and its served
tokens and returns, for each served token, how far its logit lies
below the reference's best.  With ``control=True`` it also runs the
control: the same pass one precision step below what the configuration
states -- fp8 (e4m3) weights and matmul inputs (scaled per output
column and per row) in place of bf16, and a 4-bit KV tier in place of
the 8-bit one -- and reads the reference's gap at the token the
control ranks first.

Matrix products run at ``Precision.HIGHEST``; layers run one jitted
call at a time on the layer's slice of the stacked weights, and the
head in vocabulary chunks, so the reference fits beside the weights.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QBLOCK = 256          # query rows per attention block
PAD = 512             # sequences are padded to a multiple of this
VCHUNK = 16384        # vocabulary columns per head block


def layout(m: dict) -> dict:
    """{path: (shape, std)} of a dense decoder, as the engine serves a
    dense stack (period-one blocks stacked over layers); std None for a
    norm scale.  Each matrix N(0, 1/fan_in), the two that write into the
    residual stream (``wo``, ``w_down``) a further 1/sqrt(2L) down, the
    embedding N(0, 1).

    The scale matters to the check.  With every matrix at one small std
    the stack's branches swamp the embedding, and a squared-ReLU MLP
    (whose outputs have a positive mean) maps every token to nearly the
    same vector: greedy decoding then emits one token whatever the
    prompt, so neither a fault in the KV cache nor a lower precision
    shows.  Here the token and its context keep a say in every logit."""
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


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (T, n, hd); rotation of the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def kv_quant(x, kbits: int):
    """x (T, K, hd): one symmetric absmax scale per position row,
    codes 0 .. 2^k - 1 mapped back onto [-scale, scale]."""
    q = float((1 << kbits) - 1)
    t = x.reshape(x.shape[0], -1)
    scale = jnp.max(jnp.abs(t), -1, keepdims=True) + 1e-12
    codes = jnp.clip(jnp.round((t / scale + 1.0) * 0.5 * q), 0.0, q)
    return ((codes * 2.0 - q) * (scale / q)).reshape(x.shape)


def fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``
    (the slice's absmax maps to e4m3's largest value, 448)."""
    s = jnp.max(jnp.abs(x), axis, keepdims=True) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _act(x, kind):
    if kind == "relu2":
        r = jnp.maximum(x, 0.0)
        return r * r
    if kind == "silu":
        return x * jax.nn.sigmoid(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {kind!r}")


def _attend(q, k, v, kq, vq, P):
    """q (T, K, G, hd) scaled; k, v raw and kq, vq stored rows, each
    (T, K, hd).  Causal; query rows < P read raw rows, the rest read
    stored rows."""
    T = q.shape[0]
    s_idx = jnp.arange(T)

    def block(args):
        qb, i0 = args
        q_idx = i0 + jnp.arange(qb.shape[0])
        mask = (s_idx[None, :] <= q_idx[:, None])[None, None]

        def soft(kk, vv):
            s = jnp.einsum("qkgh,skh->kgqs", qb, kk, precision=HI)
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqs,skh->qkgh", p, vv, precision=HI)

        raw, stored = soft(k, v), soft(kq, vq)
        return jnp.where((q_idx < P)[:, None, None, None], raw, stored)

    nb = T // QBLOCK
    qs = q.reshape(nb, QBLOCK, *q.shape[1:])
    out = jax.lax.map(block, (qs, jnp.arange(nb) * QBLOCK))
    return out.reshape(q.shape)


@lru_cache(maxsize=None)
def _layer_fn(mt: tuple, kv_bits: int, lowp: bool):
    m = dict(mt)
    D, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    G = H // K

    def w(a, shape):
        a = a.astype(F32).reshape(shape)
        return fp8(a, 0) if lowp else a

    def _mm(a, b):
        return jnp.matmul(fp8(a, -1) if lowp else a, b, precision=HI)

    def layer(x, layers, l, P):
        lw = jax.tree.map(lambda a: a[l], layers)
        T = x.shape[0]
        pos = jnp.arange(T)
        h = rms_norm(x, lw["norm1_0"].astype(F32))
        at = lw["attn_0"]
        q = _mm(h, w(at["wq"], (D, H * hd))).reshape(T, H, hd)
        k = _mm(h, w(at["wk"], (D, K * hd))).reshape(T, K, hd)
        v = _mm(h, w(at["wv"], (D, K * hd))).reshape(T, K, hd)
        q = rope(q, pos, m["rope_theta"]) * hd ** -0.5
        k = rope(k, pos, m["rope_theta"])
        o = _attend(q.reshape(T, K, G, hd), k, v, kv_quant(k, kv_bits),
                    kv_quant(v, kv_bits), P)
        o = _mm(o.reshape(T, H * hd), w(at["wo"], (H * hd, D)))
        mp = lw["mlp_0"]

        def mlp(hh):
            up = _mm(hh, w(mp["w_up"], (D, -1)))
            if m["gated_mlp"]:
                a = _act(_mm(hh, w(mp["w_gate"], (D, -1))), m["mlp_activation"]) * up
            else:
                a = _act(up, m["mlp_activation"])
            return _mm(a, w(mp["w_down"], (-1, D)))

        if m["parallel_block"]:
            return x + o + mlp(h)
        x = x + o
        return x + mlp(rms_norm(x, lw["norm2_0"].astype(F32)))

    return jax.jit(layer)


@lru_cache(maxsize=None)
def _embed_fn(lowp: bool):
    def embed(table, tokens):
        rows = table[tokens].astype(F32)
        return fp8(rows, -1) if lowp else rows     # per row of the table
    return jax.jit(embed)


@lru_cache(maxsize=None)
def _head_fn(lowp: bool, tied: bool, c: int):
    def head(h, mat, start):
        if tied:
            blk = jax.lax.dynamic_slice_in_dim(mat, start, c, 0).astype(F32).T
        else:
            blk = jax.lax.dynamic_slice_in_dim(mat, start, c, 1).astype(F32)
        if lowp:
            h, blk = fp8(h, -1), fp8(blk, 0)
        return _mm(h, blk)
    return jax.jit(head)


@jax.jit
def _final(x, w, rows):
    return rms_norm(x[rows], w.astype(F32))


def _hidden(params, m, tokens, P, kv_bits, lowp):
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    x = _embed_fn(lowp)(params["embed"], jnp.asarray(tok))
    layer = _layer_fn(tuple(sorted(m.items())), kv_bits, lowp)
    for l in range(m["num_layers"]):
        x = layer(x, params["layers"], l, P)
    return x


def _logit_blocks(params, m, h, lowp):
    """Yield (start, (n, c) logits) over the vocabulary; the last block
    is shifted left to fit, so columns may repeat."""
    V = m["vocab_size"]
    c = min(VCHUNK, V)
    tied = m["tie_embeddings"]
    mat = params["embed"] if tied else params["lm_head"]
    fn = _head_fn(lowp, tied, c)
    for s in range(0, V, c):
        s = min(s, V - c)
        yield s, c, fn(h, mat, s)


def _pick(blocks, idx):
    """Reference logit of column ``idx[i]`` in row ``i``."""
    out = jnp.full(idx.shape, -jnp.inf, F32)
    for s, c, lg in blocks:
        inside = (idx >= s) & (idx < s + c)
        v = jnp.take_along_axis(lg, jnp.clip(idx - s, 0, c - 1)[:, None], 1)[:, 0]
        out = jnp.where(inside, v, out)
    return out


def gaps(params, m: dict, prompt, served, kv_bits: int,
         control: bool = False) -> dict:
    """Teacher-forced reference over ``prompt`` + ``served``.

    Returns {"program": (n,) gap of each served token below the
    reference's best logit} and, with ``control``, {"control": (n,)
    gap of the token the control ranks first}."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    P, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = jnp.arange(P - 1, P - 1 + n)
    with jax.default_matmul_precision("highest"):
        h = _final(_hidden(params, m, tokens, P, kv_bits, False),
                   params["final_norm"], rows)
        best = jnp.full((n,), -jnp.inf, F32)
        blocks = []
        for s, c, lg in _logit_blocks(params, m, h, False):
            best = jnp.maximum(best, lg.max(-1))
            blocks.append((s, c, lg))
        out = {"program": np.asarray(best - _pick(blocks, jnp.asarray(served)))}
        if control:
            hc = _final(_hidden(params, m, tokens, P, max(kv_bits // 2, 1), True),
                        params["final_norm"], rows)
            cbest = jnp.full((n,), -jnp.inf, F32)
            cidx = jnp.zeros((n,), jnp.int32)
            for s, c, lg in _logit_blocks(params, m, hc, True):
                mx, ix = lg.max(-1), lg.argmax(-1).astype(jnp.int32) + s
                take = mx > cbest
                cbest = jnp.where(take, mx, cbest)
                cidx = jnp.where(take, ix, cidx)
            out["control"] = np.asarray(best - _pick(blocks, cidx))
    return out
