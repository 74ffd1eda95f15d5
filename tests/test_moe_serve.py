"""MoE serving at the tiny presets: the dropless expert layer
(models/moe.py) on the paged path, against the plain float32 reference
of the benchmark's Step-3 configuration (bench/references/step3.py).

- step3 served by the engine the benchmark runs (paged, the Pallas
  kernel's page walk, FRAC KV at k = 8) agrees in logits with the
  reference's full forward pass;
- a mixed-length bucket gives each request the tokens it gets alone;
- routing that sends every token to the same experts drops nothing;
- the routed parts of the six chips' expert shares, plus the shared
  expert once, add up to the uncut layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.references import step3 as ref
from repro.configs import get_tiny
from repro.models import model, moe
from repro.models.common import mlp
from repro.serve import engine as engine_mod
from repro.serve.engine import ServeEngine

PROMPTS = [np.arange(3, 14, dtype=np.int32), np.arange(40, 45, dtype=np.int32),
           np.arange(100, 108, dtype=np.int32)]
MAX_NEW = [6, 9, 4]
SERVED = dict(paged=True, paged_kernel=True, kv_frac_kbits=8, page_size=4)
# the reference reads these keys of a configuration's model block
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
              "q_lora_rank", "d_ff", "shared_expert_ff", "num_experts",
              "experts_held", "experts_held_start", "experts_per_token",
              "moe_interleave", "moe_renorm", "vocab_size", "mlp_activation",
              "gated_mlp", "parallel_block", "rope_theta", "norm_eps",
              "tie_embeddings")


def _m(cfg) -> dict:
    return {k: getattr(cfg, k) for k in MODEL_KEYS}


def _params(cfg, seed=3):
    """The benchmark's weights for ``cfg``: the reference's layout (its
    scales keep tokens and context in every logit), checked against
    the program's."""
    params = weights.make_params(ref.layout(_m(cfg)), seed)
    weights.check_layout(params, model.abstract_params(cfg))
    return params


def _serve(cfg, params, prompts, max_new, **kw):
    eng = ServeEngine(cfg, params, **dict(SERVED, **kw))
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
    res = eng.run()
    return eng, [res[r] for r in rids]


def _ref_logits(params, m, prompt, served):
    """Reference logits (n, V) of each served token's position, teacher
    forced over prompt + served."""
    P, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        h = ref._final(ref._hidden(params, m, tokens, P, 8, False),
                       params["final_norm"], jnp.arange(P - 1, P - 1 + n),
                       m["norm_eps"])
        (_, _, lg), = ref._logit_blocks(params, m, h, False)
    return np.asarray(lg)


def test_step3_paged_serve_matches_the_reference_logits(monkeypatch):
    """Prefill plus paged decode through the engine the cell runs, the
    logits of every served token against the reference's full forward.

    Tolerance 0.05 on logits of spread ~1 (std over the vocabulary):
    the program keeps weights, activations and the residual stream in
    bf16 (8 significant bits, ~0.4% a rounding) through 2 layers and
    the head, the reference float32 throughout; the widest difference
    read here is 0.020.  The control, one precision step down (fp8
    weights and matmul inputs, a 4-bit KV tier), differs by 0.19-0.27."""
    cfg = get_tiny("step3")
    params = _params(cfg)
    seen = []
    real = engine_mod.greedy_sample

    def record(logits):
        jax.debug.callback(lambda l: seen.append(np.asarray(l, np.float32)),
                           logits)
        return real(logits)

    monkeypatch.setattr(engine_mod, "greedy_sample", record)
    # three lanes, nothing staged: lane b serves request b, and after the
    # prefill's first tokens each loop step samples one row per lane
    _, out = _serve(cfg, params, PROMPTS, MAX_NEW, max_batch=3, stage_depth=0)
    first, steps = seen[0], seen[1:]
    m = _m(cfg)
    for b, (p, toks, n) in enumerate(zip(PROMPTS, out, MAX_NEW)):
        assert len(toks) == n
        got = np.stack([first[b]] + [s[b] for s in steps[: n - 1]])
        want = _ref_logits(params, m, p, np.asarray(toks))
        assert want.std() > 0.5
        np.testing.assert_allclose(got, want, atol=0.05, rtol=0)


@pytest.mark.parametrize("arch", ["step3", "mixtral"])
def test_mixed_length_bucket_matches_solo(arch):
    """One ragged prefill and a shared paged loop give each request the
    tokens it gets served alone: no lane's routing depends on another's."""
    if arch == "step3":
        cfg = get_tiny("step3")
        params = _params(cfg)
    else:        # without its window mixtral pages; its experts renormalize
        cfg = get_tiny("mixtral-8x7b").replace(sliding_window=0,
                                               max_decode_window=0)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert model.supports_ragged(cfg) and model.supports_paged(cfg)
    prompts = PROMPTS + [np.arange(7, 9, dtype=np.int32)]
    max_new = MAX_NEW + [5]
    eng, batched = _serve(cfg, params, prompts, max_new, max_batch=2,
                          stage_depth=2)
    assert eng.paged and eng.stats.prefills == 1 and eng.stats.admissions == 2
    for p, n, got in zip(prompts, max_new, batched):
        _, (alone,) = _serve(cfg, params, [p], [n], max_batch=1)
        assert alone == got


def _layer_input(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, cfg.d_model)), jnp.bfloat16)


def _ref_layer(cfg, p, h):
    """The reference's expert layer (layer 0 of a one-layer stack)."""
    stack = jax.tree.map(lambda a: a[None], p)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.moe_layer(jnp.asarray(h, jnp.float32), stack,
                                        _m(cfg), 0))


@pytest.mark.parametrize("decode", [False, True])
def test_skewed_routing_drops_nothing(decode):
    """A zero router ties every expert, so top-k sends all 64 tokens to
    the same 3 experts: 64 each where training's capacity routing keeps
    8.  The serving layer, in its sorted (prefill) and dense (decode)
    forms, computes every pair and matches the reference; the capacity
    path would not.  Gates renormalize here (1/3 each), so the routed
    part is as large as the shared one."""
    cfg = get_tiny("step3").replace(experts_held=0,        # all 48 here
                                    moe_renorm=True)
    m = dict(_m(cfg), num_layers=1)
    lay = {k[len("layers/moe_0/"):]: v for k, v in ref.layout(m).items()
           if k.startswith("layers/moe_0/")}
    p = jax.tree.map(lambda a: a[0], weights.make_params(lay, 5))
    p = weights._nest(dict(weights.flat(p), router=jnp.zeros_like(p["router"])))
    x = _layer_input(cfg, 64)
    gate, idx = moe.route(x, p["router"], cfg)
    assert (np.asarray(idx) == [0, 1, 2]).all()
    y, _ = moe.moe_layer(x, p, cfg, decode=decode)
    want = _ref_layer(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y, np.float32), want, atol=0.02, rtol=0.02)
    capped = moe.moe_block(x[None], p, cfg)[0]
    assert np.abs(np.asarray(capped, np.float32) - want).max() > 0.05


@pytest.mark.parametrize("experts", [moe.routed_experts, moe.dense_experts])
def test_expert_shares_sum_to_the_uncut_layer(experts):
    """Six chips hold experts 0-7, 8-15, ... of the 48: each computes
    the part of the routed result its experts give (the router keeps
    all 48 outputs); those parts plus the shared expert, which every
    chip computes alike, counted once, are the uncut reference layer.
    So in the sorted form and in the dense one."""
    full = get_tiny("step3").replace(experts_held=0)
    m = dict(_m(full), num_layers=1)
    lay = {k[len("layers/moe_0/"):]: v for k, v in ref.layout(m).items()
           if k.startswith("layers/moe_0/")}
    p = jax.tree.map(lambda a: a[0], weights.make_params(lay, 9))
    x = _layer_input(full, 40, seed=1)
    want = _ref_layer(full, p, x)
    shared = np.asarray(mlp(x, p["shared"], full), np.float32)
    total = shared
    for s in range(6):
        cfg = full.replace(experts_held=8, experts_held_start=8 * s)
        share = {k: (v[8 * s: 8 * (s + 1)] if k.startswith("w_") else v)
                 for k, v in p.items()}
        gate, idx = moe.route(x, share["router"], cfg)
        part = np.asarray(experts(x, share, cfg, gate, idx))
        # a share's part is the uncut layer's restricted to its experts
        held = (np.asarray(idx) // 8 == s)
        assert (np.abs(part).max(-1) > 0).sum() == held.any(-1).sum()
        total = total + part
    np.testing.assert_allclose(total, want, atol=0.02, rtol=0.02)


def test_pad_slots_take_no_routed_expert():
    """A ragged prefill's pad slots, all one token id, would route
    alike and pile onto the same experts; the sorted form leaves every
    pair of a token that is not ``live`` out of the grouped matmul, so
    its routed part is zero, and the live tokens' parts are the ones
    they get without a mask, bit for bit."""
    cfg = get_tiny("step3").replace(experts_held=0)
    m = dict(_m(cfg), num_layers=1)
    lay = {k[len("layers/moe_0/"):]: v for k, v in ref.layout(m).items()
           if k.startswith("layers/moe_0/")}
    p = jax.tree.map(lambda a: a[0], weights.make_params(lay, 11))
    x = _layer_input(cfg, 24, seed=2)
    live = jnp.asarray(np.arange(24) % 3 != 0)
    gate, idx = moe.route(x, p["router"], cfg)
    full = np.asarray(moe.routed_experts(x, p, cfg, gate, idx))
    got = np.asarray(moe.routed_experts(x, p, cfg, gate, idx, live))
    keep = np.asarray(live)
    assert (np.abs(full[~keep]).max(-1) > 0).all()
    assert (got[~keep] == 0).all()
    np.testing.assert_array_equal(got[keep], full[keep])
