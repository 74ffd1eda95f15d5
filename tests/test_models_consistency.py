"""Cross-path model invariants: decode == teacher-forced forward,
scan == step-by-step, SWA == masked full attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.models import model
from repro.models.common import attention, windowed_prefill_attention

# bf16 accumulation tolerance on logits: decode recomputes attention
# against a cache built by the chunked prefill, so ~0.1-scale drift on
# O(10)-scale logits is expected; argmax agreement is the strong check.
TOL = 0.25


def _roundtrip(arch, n_steps=3):
    cfg = get_tiny(arch)
    params = model.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    B, S = 2, 16
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": toks}
    if cfg.family == "audio":
        batch["enc_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.encoder_seq, cfg.d_model), np.float32)
        )
    _, cache = model.prefill(cfg, params, batch)
    seq = toks
    for i in range(n_steps):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (B,)), jnp.int32)
        logits_d, cache = model.decode_step(cfg, params, cache, tok,
                                            jnp.int32(S + i))
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        full_batch = dict(batch)
        full_batch["tokens"] = seq
        logits_f = model.forward(cfg, params, full_batch)[:, -1]
        ld = np.asarray(logits_d, np.float32)
        lf = np.asarray(logits_f, np.float32)
        d = np.abs(ld - lf).max()
        assert d < TOL, f"{arch} step {i}: decode/forward drift {d}"
        # always: decode's argmax token must be drift-close to the
        # forward max (runs even when every logit is near-tied)
        near = lf >= lf.max(-1, keepdims=True) - 2 * TOL
        picked = near[np.arange(near.shape[0]), ld.argmax(-1)]
        assert picked.all(), f"{arch} step {i}: decode argmax outside tol"
        # strict argmax agreement only where the top-2 gap clears the
        # documented tolerance; random-init logits can be tied to
        # within bf16 noise.  Fixed gate (not the observed drift) so a
        # regression can't widen its own exemption.
        srt = np.sort(lf, axis=-1)
        confident = (srt[:, -1] - srt[:, -2]) > 2 * TOL
        if confident.any():
            agree = (ld.argmax(-1)[confident]
                     == lf.argmax(-1)[confident]).mean()
            assert agree >= 0.5, f"{arch} step {i}: argmax agreement {agree}"


@pytest.mark.parametrize("arch", [
    "llama3.2-3b",        # dense + tied embeddings
    "stablelm-12b",       # parallel block
    "nemotron-4-15b",     # squared-ReLU
    "rwkv6-1.6b",         # ssm path
    "jamba-1.5-large-398b",  # hybrid mamba+attn+moe
    "whisper-medium",     # enc-dec + cross-attn
])
def test_decode_matches_forward(arch):
    _roundtrip(arch)


def test_moe_decode_matches_forward_loosely():
    """Serving's expert layer (prefill and decode) is dropless; the
    training forward routes with per-group capacity, set high enough
    here (4.0) that nothing drops.  The two compute the same experts
    with different grouping and rounding (fp32 accumulation against
    the capacity path's bf16 einsums), so the logits agree loosely."""
    cfg = get_tiny("mixtral-8x7b").replace(sliding_window=0, max_decode_window=0,
                                           capacity_factor=4.0)
    params = model.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    B, S = 2, 16
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S)), jnp.int32)
    _, cache = model.prefill(cfg, params, {"tokens": toks})
    tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (B,)), jnp.int32)
    logits_d, _ = model.decode_step(cfg, params, cache, tok, jnp.int32(S))
    seq = jnp.concatenate([toks, tok[:, None]], axis=1)
    logits_f = model.forward(cfg, params, {"tokens": seq})[:, -1]
    d = np.abs(np.asarray(logits_d, np.float32)
               - np.asarray(logits_f, np.float32)).max()
    assert d < 0.15, f"moe decode/forward drift {d}"


def test_swa_rolling_cache_decode():
    """Mixtral tiny with window: decode after prefill matches forward."""
    cfg = get_tiny("mixtral-8x7b")   # window 16 = S
    params = model.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    B, S = 2, 24                      # prompt longer than window
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S)), jnp.int32)
    _, cache = model.prefill(cfg, params, {"tokens": toks})
    assert cache["k_0"].shape[2] == cfg.max_decode_window
    tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (B,)), jnp.int32)
    logits_d, _ = model.decode_step(cfg, params, cache, tok, jnp.int32(S))
    seq = jnp.concatenate([toks, tok[:, None]], axis=1)
    logits_f = model.forward(cfg, params, {"tokens": seq})[:, -1]
    d = np.abs(np.asarray(logits_d, np.float32)
               - np.asarray(logits_f, np.float32)).max()
    assert d < 0.15, f"SWA rolling-cache drift {d}"


def test_windowed_attention_equals_masked_full():
    rng = np.random.default_rng(0)
    B, S, H, K, hd, W, c = 2, 64, 4, 2, 16, 16, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
    fast = windowed_prefill_attention(q, k, v, window=W, chunk=c)
    slow = attention(q, k, v, causal=True, window=W, chunk=0)
    assert np.allclose(np.asarray(fast), np.asarray(slow), atol=1e-5)


def test_rwkv_sequence_equals_stepwise():
    from repro.models import rwkv

    cfg = get_tiny("rwkv6-1.6b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(0)
    B, S = 2, 12
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16)
    seqout = rwkv.rwkv_layer_sequence(x, lp, cfg, lp["ln1"], lp["ln2"])
    st = rwkv.init_rwkv_state(cfg, B)
    outs = []
    for t in range(S):
        o, st = rwkv.rwkv_layer_step(x[:, t], st, lp, cfg, lp["ln1"], lp["ln2"])
        outs.append(o)
    stepout = jnp.stack(outs, axis=1)
    d = np.abs(np.asarray(seqout, np.float32) - np.asarray(stepout, np.float32)).max()
    assert d < 1e-2


def test_mamba_block_decode_consistency():
    from repro.models import mamba

    cfg = get_tiny("jamba-1.5-large-398b")
    specs = mamba.mamba_param_specs(cfg)
    from repro.models.common import tree_init

    p = tree_init(specs, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 2, 10
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16)
    full = mamba.mamba_block(x, p, cfg)
    st = mamba.init_mamba_state(cfg, B)
    outs = []
    for t in range(S):
        o, st = mamba.mamba_decode_step(x[:, t], st, p, cfg)
        outs.append(o)
    stepped = jnp.stack(outs, axis=1)
    d = np.abs(np.asarray(full, np.float32) - np.asarray(stepped, np.float32)).max()
    assert d < 5e-2


def test_rwkv_chunked_equals_step_form():
    """§Perf hillclimb: the chunked matmul-form wkv must match the
    step-scan form (same arithmetic, re-chunked)."""
    from repro.models import rwkv

    cfg = get_tiny("rwkv6-1.6b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(0)
    B, S = 2, 64
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16)
    ref = rwkv.rwkv_layer_sequence(x, lp, cfg, lp["ln1"], lp["ln2"])
    for ch in (8, 32, 64):
        got = rwkv.rwkv_layer_chunked(x, lp, cfg, lp["ln1"], lp["ln2"], chunk=ch)
        d = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32)).max()
        assert d < 2e-2, (ch, d)
    # model-level: chunked config reproduces step-form logits
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 32)), jnp.int32)
    l_step = model.forward(cfg, params, {"tokens": toks})
    l_chunk = model.forward(cfg.replace(rwkv_chunk=16), params, {"tokens": toks})
    d = np.abs(np.asarray(l_step, np.float32) - np.asarray(l_chunk, np.float32)).max()
    assert d < 0.05, d


def test_sp2_layout_expert_specs():
    from repro.sharding.rules import spec_for_dims
    from jax.sharding import PartitionSpec as P

    class M:
        shape = {"data": 16, "model": 16}

    # 2D expert sharding: E over data, FFN over model, resident weights
    assert spec_for_dims((128, 5120, 8192), ("experts", "embed", "mlp"),
                         M(), layout="sp2") == P("data", None, "model")
    # non-expert weights fall back to the sp rule (FSDP only)
    assert spec_for_dims((5120, 40, 128), ("embed", "heads", "head_dim"),
                         M(), layout="sp2") == P("data", None, None)
