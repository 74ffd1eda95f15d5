"""Compile every Pallas kernel of the main path for a TPU v5e.

Nothing runs: each test lowers a kernel with ``interpret=False`` for a
device of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler installed next to jax, which refuses what the chip would
refuse (unsupported casts and relayouts, misaligned blocks, VMEM over
budget).  Shapes are the real ones: llama3.2-3b's decode attention,
FRAC tensors of 16M values, the paper's NTT and SHA3 batches.  Each
test asserts the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described in a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.frac_pack import frac_quant_pack as fq
from repro.kernels.ntt import ops as ntt_ops
from repro.kernels.paged_attn import ops as paged_ops
from repro.kernels.sha3.sha3 import keccak_f_pallas

FRAC_N = 1 << 24


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A device of the described topology, with the persistent compile
    cache off: a TPU executable cached here could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_attention_llama3_2_3b(one_chip):
    cfg = get_config("llama3.2-3b")
    B, P, ps, max_pages = 8, 512, 16, 128          # 2048 slots per lane
    H, K, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4
    compiled = _compile(
        lambda q, pk, pv, pt, pos, layer: paged_ops.paged_attention(
            q, pk, pv, pt, pos, layer, mode="pallas"),
        one_chip,
        ((B, H, hd), jnp.bfloat16), ((L, P, ps, K * hd), jnp.bfloat16),
        ((L, P, ps, K * hd), jnp.bfloat16), ((B, max_pages), jnp.int32),
        ((B,), jnp.int32), ((), jnp.int32))
    # the stacked pool stays in HBM: the program's scratch holds a few
    # chunks of pages, never one layer's (P, ps, K*hd) pool
    pool_bytes = P * ps * K * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_paged_attention_step3(one_chip):
    """Step-3's decode attention as the benchmark serves it: one KV head
    under 64 query heads of 256, 8 lanes, 4 stacked layers."""
    cfg = get_config("step3")
    B, P, ps, max_pages = 8, 1024, 16, 128
    H, K, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4
    assert (H, K, hd) == (64, 1, 256)
    compiled = _compile(
        lambda q, pk, pv, pt, pos, layer: paged_ops.paged_attention(
            q, pk, pv, pt, pos, layer, mode="pallas"),
        one_chip,
        ((B, H, hd), jnp.bfloat16), ((L, P, ps, K * hd), jnp.bfloat16),
        ((L, P, ps, K * hd), jnp.bfloat16), ((B, max_pages), jnp.int32),
        ((B,), jnp.int32), ((), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < P * ps * K * hd * 2


def test_paged_decode_loop_stablelm_12b_moves_no_pool(one_chip,
                                                      monkeypatch):
    """The benchmark's stablelm-12b stage (10 layers at published
    widths, hd 160) through the paged decode loop with the Pallas
    kernel, 8 lanes and 8 staged: the chip's program updates the bf16
    pool in place.  No op but the loop's parameters, its carry and the
    row scatter yields an array the size of the stack or of one
    layer's pool, and the temp stays under one layer's pool."""
    from test_serve_paged import pool_movers

    from repro.models import model
    from repro.models.common import is_leaf_spec
    from repro.serve.engine import build_paged_decode_loop

    # off a TPU the auto mode is the jnp walk; the chip runs the kernel
    monkeypatch.setenv(paged_ops.ENV_VAR, "pallas")
    cfg = get_config("stablelm-12b").replace(num_layers=10)
    B, Q, mp, P, ps = 8, 8, 64, 1000, 16
    lanes = cfg.num_kv_heads * cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                        model.paged_pool_specs(cfg, P, ps),
                        is_leaf=is_leaf_spec)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params(cfg))
    i32 = lambda *shape: sds(shape, jnp.int32)          # noqa: E731
    loop = build_paged_decode_loop(cfg, kv_kbits=8, out_cap=512,
                                   page_size=ps, paged_kernel=True)
    compiled = loop.lower(params, pool, i32(B, mp), i32(P), i32(), i32(B),
                          i32(B), i32(Q), i32(Q), i32(Q, mp),
                          i32(B + Q)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer = P * ps * lanes                     # one layer's pool, elements
    assert pool_movers(text, {cfg.num_layers * layer, layer}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer  # bf16


@pytest.mark.parametrize("k", [8, 11])
def test_frac_quant_pack(one_chip, k):
    compiled = _compile(lambda x: fq.quant_pack(x, k), one_chip,
                        ((FRAC_N,), jnp.float32))
    # the lane transposes stay near the tensor's own size: a layout
    # whose minor dim is one segment (4-32 items) pads to 128 lanes and
    # blew a 394M-value checkpoint leaf up to 50 GB on the chip
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 4 * FRAC_N


@pytest.mark.parametrize("n", [1000, 40 * 256])  # one tile, narrower than 128
def test_frac_small_tensor_one_tile(one_chip, n):
    nb = -(-n // fq.BLOCK)
    _compile(lambda x: fq.quant_pack(x, 11), one_chip, ((n,), jnp.float32))
    _compile(lambda w, s: fq.unpack_dequant(w, s, 11, n), one_chip,
             ((nb * fq.words_per_block(11),), jnp.uint32),
             ((nb,), jnp.float32))


def test_frac_quant_pack_stochastic(one_chip):
    _compile(lambda x, key: fq.quant_pack(x, 8, rng=key), one_chip,
             ((FRAC_N,), jnp.float32), ((2,), jnp.uint32))


@pytest.mark.parametrize("k", [8, 11])
def test_frac_unpack_dequant(one_chip, k):
    nb = FRAC_N // fq.BLOCK
    compiled = _compile(
        lambda w, s: fq.unpack_dequant(w, s, k, FRAC_N), one_chip,
        ((nb * fq.words_per_block(k),), jnp.uint32), ((nb,), jnp.float32))
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 4 * FRAC_N


@pytest.mark.parametrize("n", [1024, 2048])    # 2048: q's largest
def test_ntt(one_chip, n):
    _compile(lambda a, b: ntt_ops.negacyclic_mul(a, b), one_chip,
             ((8, n), jnp.int32), ((8, n), jnp.int32))


def test_ntt_32k(one_chip):
    _compile(ntt_ops.ntt_32k, one_chip, ((32768,), jnp.int32))


@pytest.mark.parametrize("batch", [64, 1000])
def test_sha3_keccak(one_chip, batch):
    _compile(keccak_f_pallas, one_chip, ((batch, 25, 2), jnp.uint32))
