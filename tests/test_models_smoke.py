"""Per-arch smoke tests (required deliverable f): reduced same-family
configs, one forward/train step on CPU, output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_tiny
from repro.data.pipeline import make_batch
from repro.models import model
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.step import make_train_step

B, S = 2, 32


def _batch(cfg):
    return make_batch(cfg, B, S, step=0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_tiny(arch)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = jax.jit(lambda p, b: model.forward(cfg, p, b))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss = float(model.loss_fn(cfg, params, batch))
    assert np.isfinite(loss)
    # random init ≈ uniform prediction
    assert loss == pytest.approx(np.log(cfg.vocab_size), rel=0.25)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_tiny(arch)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, AdamWConfig())
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, warmup_steps=1)))
    p2, o2, loss = step(params, opt, _batch(cfg))
    assert np.isfinite(float(loss))
    # params actually moved
    moved = any(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    )
    assert moved
    for leaf in jax.tree.leaves(p2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch):
    cfg = get_tiny(arch)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, cache = jax.jit(lambda p, b: model.prefill(cfg, p, b))(params, batch)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    logits_d, cache2 = jax.jit(
        lambda p, c, t: model.decode_step(cfg, p, c, t, jnp.int32(S))
    )(params, cache, tok)
    assert logits_d.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits_d, np.float32)).all()
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(cache2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_counts(arch):
    """Full (published) configs build abstractly and land on the public
    param counts — no allocation happens here."""
    expect = {
        "mixtral-8x7b": (46.7e9, 0.02),
        "llama4-maverick-400b-a17b": (400e9, 0.05),
        "stablelm-12b": (12.1e9, 0.05),
        "minitron-8b": (8e9, 0.06),
        "nemotron-4-15b": (15.6e9, 0.05),
        "llama3.2-3b": (3.2e9, 0.05),
        "jamba-1.5-large-398b": (398e9, 0.02),
        "pixtral-12b": (12.3e9, 0.05),
        "rwkv6-1.6b": (1.6e9, 0.05),
        "whisper-medium": (0.77e9, 0.05),
        # 321B published: the vision tower and the five dense layers
        # (0-3, 60) are not in the repo's uniform MoE stack
        "step3": (321e9, 0.03),
    }[arch]
    cfg = get_config(arch)
    n = model.count_params(cfg)
    assert n == pytest.approx(expect[0], rel=expect[1])
    # active <= total; strictly less for MoE
    na = model.count_active_params(cfg)
    assert na <= n
    if cfg.is_moe:
        assert na < n
