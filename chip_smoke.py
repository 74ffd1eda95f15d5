#!/usr/bin/env python3
"""On-chip smoke test: the serving main path on a TPU, end to end.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the sharded-mesh path

One process does every phase (a chip belongs to one process; this
script starts no child that touches JAX):

  (a) device    platform, kind, count, memory stats.
  (b) kernels   every Pallas kernel of the main path, compiled, at real
                widths against its oracle: paged attention at
                llama3.2-3b shapes (gather_pages + common.attention),
                FRAC quant_pack/unpack_dequant at k=8 and k=11 on 16M
                values (core/frac/codec, bit-exact), NTT negacyclic_mul
                (kernels/ntt/ref.py, exact), SHA3-256 (hashlib, exact).
  (c) serve     8 requests, prompts 256-2048 tokens, 32-64 new tokens,
                through the launcher's paged engine at full llama3.2-3b
                width with the compiled paged-attention kernel and the
                FRAC KV dial.
  (model)       prefill + paged decode steps (compiled kernel) vs
                ``model.forward`` on logits.
  (d) ckpt      a frac8 save/restore of served weights through
                train/checkpoint.py (the compiled FRAC kernels on the
                recycled-flash tier's device path).

``--chips 4`` runs only the mesh phase: stablelm-12b at full depth on
a (data=1, model=4) mesh, plus a depth-cut copy on the mesh vs on one
of its devices (logits must agree).  Weights are random, from --seed.

Every phase runs even after one fails (its traceback is printed);
any failure exits non-zero without the JSON line.  No
TPU (or no ``src/repro`` next to this file) exits non-zero at once.
The last stdout line is one JSON object: ok + the device as JAX
reports it.  The persistent compile cache is JAX_COMPILATION_CACHE_DIR
when set, else ``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
LLAMA = "llama3.2-3b"
STABLELM = "stablelm-12b"
FRAC_N = 1 << 24                # values per FRAC kernel check (16M)


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kw):
    """(result, seconds) with the device work finished inside."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device(ctx) -> None:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)}")
    for dev in devs:
        stats = dev.memory_stats() or {}
        keys = ("bytes_limit", "bytes_in_use", "peak_bytes_in_use")
        log(f"[device] {dev.id}: " + " ".join(
            f"{k}={stats[k]}" for k in keys if k in stats))
    from repro import hw

    ctx["chip"] = hw.attached()        # raises for a kind off the table
    log(f"[device] peaks: {ctx['chip']}")


# ---------------------------------------------------------------------------
# (b) kernels at real widths, compiled, against their oracles
# ---------------------------------------------------------------------------


def _paged_fixture(rng, B, H, K, hd, ps, positions, dtype, layers=3):
    import jax.numpy as jnp
    import numpy as np

    pages = [p // ps + 1 for p in positions]
    mp = max(pages)
    n_pages = 1 << max(sum(pages), 511).bit_length()   # >= 512, + trash 0
    ids = rng.permutation(np.arange(1, n_pages))
    table = np.full((B, mp), -1, np.int32)
    used = 0
    for b, n in enumerate(pages):
        table[b, :n] = ids[used:used + n]
        used += n
    # the stacked pool as the engine stores it: (layers, P, ps, K*hd)
    pk = rng.standard_normal((layers, n_pages, ps, K * hd), np.float32)
    pv = rng.standard_normal((layers, n_pages, ps, K * hd), np.float32)
    q = rng.standard_normal((B, H, hd), np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pk, dtype),
            jnp.asarray(pv, dtype), jnp.asarray(table),
            jnp.asarray(np.asarray(positions, np.int32)))


def kernel_paged_attention(ctx) -> None:
    """llama3.2-3b decode shapes, the last layer of a 3-layer stacked
    pool read in place; the trash page is NaN-poisoned and is DMA'd
    for every lane whose last chunk has unallocated columns."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attn import ops as pops
    from repro.models.common import attention, gather_pages

    cfg = get_config(LLAMA)
    H, K, hd, ps = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 16
    positions = [2047, 1500, 900, 301, 17, 0, 1200, 640]
    q, pk, pv, table, pos = _paged_fixture(
        np.random.default_rng(ctx["seed"]), len(positions), H, K, hd, ps,
        positions, jnp.bfloat16)
    pk = pk.at[:, 0].set(jnp.nan)
    pv = pv.at[:, 0].set(jnp.nan)
    layer = jnp.int32(pk.shape[0] - 1)
    fused = jax.jit(lambda *a: pops.paged_attention(*a, mode="pallas"))
    out, t_cold = timed(fused, q, pk, pv, table, pos, layer)
    out, t_warm = timed(fused, q, pk, pv, table, pos, layer)

    @jax.jit
    def oracle(q, pk, pv, table, pos, layer):
        kv = [gather_pages(p.astype(jnp.float32), table, layer).reshape(
            q.shape[0], -1, K, hd) for p in (pk, pv)]
        return attention(q.astype(jnp.float32)[:, None], *kv, causal=False,
                         kv_valid_len=pos + 1,
                         q_positions=pos[:, None])[:, 0]

    ref = oracle(q, pk, pv, table, pos, layer)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    # Tolerance: the fp32 oracle sees the same bf16 inputs; the kernel
    # differs only by rounding its (B, H, hd) output to bf16 (half an
    # ulp, 2^-9 of |out| <= max|v|) and its fp32 accumulation order.
    # 2^-7·max|v| leaves 4x headroom; a wrong page, slot mask or
    # position shifts outputs by O(max|v|).
    vmax = float(jnp.max(jnp.abs(pv[layer, 1:].astype(jnp.float32))))
    tol = 2.0 ** -7 * vmax
    log(f"[kernels] paged_attention B={len(positions)} H={H} K={K} "
        f"hd={hd} ps={ps} pool={pk.shape[:2]} layers x pages "
        f"layer={int(layer)} table={table.shape} "
        f"max_abs_err={err:.3e} tol={tol:.3e} cold_s={t_cold:.3f} "
        f"warm_s={t_warm:.6f}")
    check(bool(jnp.isfinite(out).all()), "paged attention: non-finite "
          "output (trash-page poison leaked)")
    check(err <= tol, f"paged attention: error {err} > {tol}")


def kernel_frac(ctx) -> None:
    """quant_pack / unpack_dequant at k=8 and k=11 on 16M values,
    bit-exact against the jnp codec run on the host CPU device (the
    IEEE reference arithmetic)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.frac import codec
    from repro.kernels.frac_pack import frac_quant_pack as fq

    n = FRAC_N
    x_np = np.random.default_rng(ctx["seed"] + 1).standard_normal(
        n, np.float32) * 3.0
    x = jnp.asarray(x_np)
    cpu = jax.devices("cpu")[0]
    x_cpu = jax.device_put(x_np, cpu)
    # how far the chip's f32 division lands from IEEE (information: the
    # encode kernel repairs its quotient with frac_quant_pack.div_rn)
    xb = x_np.reshape(-1, 256)
    s_np = np.abs(xb).max(axis=1, keepdims=True) + np.float32(1e-12)
    q_dev = np.asarray(jax.jit(lambda a, s: a / s)(jnp.asarray(xb),
                                                   jnp.asarray(s_np)))
    ulps = np.abs(q_dev.view(np.int32).astype(np.int64)
                  - (xb / s_np).view(np.int32).astype(np.int64))
    log(f"[kernels] device f32 division vs IEEE on {n} quotients: "
        f"1 ulp off={int(np.sum(ulps == 1))} 2+ ulps off="
        f"{int(np.sum(ulps >= 2))} max_ulps={int(ulps.max())}")
    key = jax.random.PRNGKey(ctx["seed"])
    bad_k = []
    for k, rng in ((8, None), (11, None), (8, key)):
        enc = jax.jit(lambda a, k=k, rng=rng: fq.quant_pack(a, k, rng=rng))
        dec = jax.jit(lambda w, s, k=k: fq.unpack_dequant(w, s, k, n))
        (words, scales), t_enc = timed(enc, x)
        _, t_enc = timed(enc, x)
        back, t_dec = timed(dec, words, scales)
        _, t_dec = timed(dec, words, scales)
        with jax.default_device(cpu):
            codes, s_ref = codec.quantize_blocks(
                x_cpu, k, rng=None if rng is None else jax.device_put(rng, cpu))
            w_ref = codec.pack_bits(codes, k)
            b_ref = codec.dequantize_blocks(codes, s_ref, k, n)
        # the codec's own jnp path on the chip, for comparison only
        # (XLA's f32 division on the TPU is not correctly rounded)
        xla_codes, _ = jax.jit(lambda a, k=k, rng=rng: codec.quantize_blocks(
            a, k, rng=rng))(x)
        xla_bad = int(np.sum(np.asarray(xla_codes) != np.asarray(codes)))
        w_bad = int(np.sum(np.asarray(words) != np.asarray(w_ref)))
        s_bad = int(np.sum(np.asarray(scales) != np.asarray(s_ref)))
        b_bad = int(np.sum(np.asarray(back) != np.asarray(b_ref)))
        # decode alone, from the oracle's own words
        d_bad = int(np.sum(np.asarray(dec(jnp.asarray(np.asarray(w_ref)),
                                          jnp.asarray(np.asarray(s_ref))))
                           != np.asarray(b_ref)))
        name = f"k={k}" + ("" if rng is None else " stochastic")
        log(f"[kernels] frac {name} n={n} words_mismatch={w_bad} "
            f"scales_mismatch={s_bad} decode_mismatch={b_bad} "
            f"decode_of_oracle_words_mismatch={d_bad} "
            f"encode_s={t_enc:.6f} decode_s={t_dec:.6f} "
            f"(xla_jnp_codec_on_device_code_mismatch={xla_bad}, not checked)")
        if w_bad:
            got = np.asarray(codec.unpack_bits(jnp.asarray(np.asarray(words)),
                                               k, n))
            for i in np.flatnonzero(got != np.asarray(codes))[:5]:
                log(f"[kernels]   code {i}: kernel={got[i]} "
                    f"oracle={int(codes[i])} x={x_np[i]!r} "
                    f"scale={float(s_np[i // 256, 0])!r}")
        if w_bad or s_bad or b_bad or d_bad:
            bad_k.append(name)
    check(not bad_k, f"frac {bad_k}: kernel not bit-exact against the codec")


def kernel_ntt(ctx) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ntt import ops, ref

    rng = np.random.default_rng(ctx["seed"] + 2)
    a = jnp.asarray(rng.integers(0, ref.Q, (8, 2048)), jnp.int32)
    b = jnp.asarray(rng.integers(0, ref.Q, (8, 2048)), jnp.int32)
    got, t = timed(ops.negacyclic_mul, a, b)
    want = ref.negacyclic_mul(a, b)
    bad = int(jnp.sum(got != want))
    log(f"[kernels] ntt negacyclic_mul 8x2048 q={ref.Q} mismatch={bad} "
        f"cold_s={t:.3f}")
    check(bad == 0, "ntt: kernel differs from ref.negacyclic_mul")


def kernel_sha3(ctx) -> None:
    import numpy as np

    from repro.kernels.sha3 import ops

    rng = np.random.default_rng(ctx["seed"] + 3)
    msgs = [rng.integers(0, 256, int(m), dtype=np.uint8).tobytes()
            for m in rng.integers(0, 700, 256)]
    t0 = time.perf_counter()
    got = ops.sha3_256(msgs)
    t = time.perf_counter() - t0
    bad = sum(g != hashlib.sha3_256(m).digest() for g, m in zip(got, msgs))
    log(f"[kernels] sha3_256 {len(msgs)} msgs mismatch={bad} "
        f"cold_s={t:.3f}")
    check(bad == 0, "sha3: kernel digests differ from hashlib")


def phase_kernels(ctx) -> None:
    run_all([(fn.__name__, fn) for fn in (
        kernel_paged_attention, kernel_frac, kernel_ntt, kernel_sha3)],
        ctx)


def run_all(steps, ctx) -> None:
    """Run every step even after one fails (each failure is printed
    with its traceback), then raise if any failed."""
    failed = []
    for name, fn in steps:
        t0 = time.perf_counter()
        try:
            fn(ctx)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[smoke] {name} FAILED after {time.perf_counter() - t0:.1f} s")
        else:
            log(f"[smoke] {name} ok in {time.perf_counter() - t0:.1f} s")
    check(not failed, f"failed: {failed}")


# ---------------------------------------------------------------------------
# (c) serve at full llama3.2-3b width
# ---------------------------------------------------------------------------


def _served_stats(eng, s0_tokens, s0_syncs, s0_decode_s, s0_steps):
    s = eng.stats
    return (s.tokens - s0_tokens, s.host_syncs - s0_syncs,
            s.decode_s - s0_decode_s, s.decode_steps - s0_steps)


def phase_serve(ctx) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.ese.meter import MeterConfig, SustainabilityMeter
    from repro.launch import serve as launcher

    cfg = get_config(LLAMA)
    dev = jax.devices()[0]
    label = f"{dev.platform}:{dev.device_kind}"
    params, t_init = timed(launcher.load_params, cfg, seed=ctx["seed"])
    log(f"[serve] {cfg.name} params={sum(p.size for p in jax.tree.leaves(params))} "
        f"init_s={t_init:.1f}")
    ctx["params"], ctx["cfg"] = params, cfg
    rng = np.random.default_rng(ctx["seed"] + 4)
    lens = [2048, 1792, 1536, 1280, 1024, 768, 512, 256]
    max_new = [64, 32, 48, 40, 56, 36, 60, 44]
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    meter = SustainabilityMeter(
        MeterConfig(chips=1, flat_w=ctx["chip"].tdp_w), name="smoke")
    t0 = time.perf_counter()
    eng, out = launcher.serve_requests(
        cfg, params, prompts, max_new, max_batch=8, paged=True,
        page_size=16, paged_kernel=True, kv_frac_kbits=8, meter=meter)
    t_cold = time.perf_counter() - t0
    check(eng.paged and eng.paged_kernel, "engine not on the paged kernel")
    s0 = (eng.stats.tokens, eng.stats.host_syncs, eng.stats.decode_s,
          eng.stats.decode_steps)
    t0 = time.perf_counter()
    _, out2 = launcher.serve_requests(cfg, params, prompts, max_new,
                                      engine=eng)
    t_warm = time.perf_counter() - t0
    toks, syncs, dec_s, steps = _served_stats(eng, *s0)
    for (rid, o), m in zip(sorted(out.items()), max_new):
        check(len(o) == m, f"request {rid}: {len(o)} tokens, wanted {m}")
        check(all(0 <= t < cfg.vocab_size for t in o),
              f"request {rid}: token id out of range")
    check(list(out.values()) == list(out2.values()),
          "warm serve of the same requests gave different tokens")
    decode_toks = toks - len(prompts)          # first tokens: prefill
    rep = eng.energy_report()
    log(f"[serve] requests={len(prompts)} prompt_lens={lens} "
        f"max_new={max_new} tokens_served={sum(map(len, out.values()))}")
    log(f"[serve] device={label} cold_wall_s={t_cold:.2f} "
        f"warm_wall_s={t_warm:.2f} compile_s~{t_cold - t_warm:.2f} "
        f"(cold minus warm wall) host_syncs={syncs} decode_steps={steps}")
    log(f"[serve] device={label} steady_decode_tokens_per_s="
        f"{decode_toks / dec_s:.1f} (warm run: {decode_toks} decode tokens "
        f"in {dec_s:.3f} s) e2e_tokens_per_s={toks / t_warm:.1f} "
        f"kv_bytes_full={eng.stats.kv_bytes_full} "
        f"kv_bytes_frac={eng.stats.kv_bytes_frac} "
        f"modeled_J_per_token={rep.operational_j / max(eng.stats.tokens, 1):.3f}")


def model_check(ctx) -> None:
    """Prefill + paged decode (compiled kernel) vs ``model.forward``
    over the same tokens, on logits (teacher-forced, no sampling)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model
    from repro.models.common import is_leaf_spec
    from repro.serve import paging

    cfg, params = ctx["cfg"], ctx["params"]
    ps, steps = 16, 4
    lens = np.asarray([256, 200], np.int32)
    S = int(lens.max())
    T = S + steps
    rng = np.random.default_rng(ctx["seed"] + 5)
    seq = rng.integers(1, cfg.vocab_size, (len(lens), T)).astype(np.int32)
    prompts = np.where(np.arange(S)[None, :] < lens[:, None], seq[:, :S], 0)
    ref = jax.jit(lambda p, t: model.forward(cfg, p, {"tokens": t}))(
        params, jnp.asarray(seq)).astype(jnp.float32)
    logits0, cache = jax.jit(
        lambda p, t, n: model.prefill(cfg, p, {"tokens": t}, lengths=n))(
        params, jnp.asarray(prompts), jnp.asarray(lens))
    # every lane owns its whole horizon of pages up front
    horizon = [paging.pages_for(int(n) + steps, ps) for n in lens]
    table = np.full((len(lens), max(horizon)), -1, np.int32)
    nxt = 1
    for b, h in enumerate(horizon):
        table[b, :h] = np.arange(nxt, nxt + h)
        nxt += h
    n_pages = nxt
    pi, oi = paging.pool_scatter_indices(table, lens, S, n_pages, ps)
    pool = jax.tree.map(
        lambda spec, leaf: paging.fill_pool(
            jnp.zeros(spec.shape, leaf.dtype), leaf, jnp.asarray(pi),
            jnp.asarray(oi)),
        model.paged_pool_specs(cfg, n_pages, ps), cache, is_leaf=is_leaf_spec)
    del cache
    lanes = np.arange(len(lens))
    want = jnp.stack([ref[lanes, lens - 1 + i] for i in range(steps + 1)])

    def decode(paged_kernel: bool, pool):
        step = jax.jit(lambda p, pool, t, pos: model.decode_step_paged(
            cfg, p, pool, jnp.asarray(table), t, pos,
            paged_kernel=paged_kernel))
        out = [logits0[:, 0].astype(jnp.float32)]
        for i in range(steps):
            pos = lens + i
            logits, pool = step(params, pool, jnp.asarray(seq[lanes, pos]),
                                jnp.asarray(pos))
            out.append(logits.astype(jnp.float32))
        return jnp.stack(out)

    got = decode(True, pool)
    gathered = decode(False, pool)      # the gather read, for reference
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    err_gather = float(jnp.max(jnp.abs(gathered - want)))
    err_kg = float(jnp.max(jnp.abs(got - gathered)))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    # Tolerance: both paths run bf16 weights/activations with fp32
    # accumulation, but round K/V, scores and residual sums at
    # different points through 28 layers (prefill + paged cache +
    # kernel vs one full-sequence forward), so logits differ by a few
    # bf16 steps (2^-8 relative) of the logit scale.  A wrong page,
    # slot, position or mask moves them by O(scale).  2^-4 of the
    # largest |logit| sits between the two.
    tol = 2.0 ** -4 * scale
    log(f"[serve] model check lanes={lens.tolist()} decode_steps={steps} "
        f"max_abs_logit_err={err:.4e} logit_scale={scale:.4e} "
        f"tol={tol:.4e} top1_agreement={agree:.3f} "
        f"gather_read_err={err_gather:.4e} kernel_vs_gather={err_kg:.4e}")
    check(bool(jnp.isfinite(got).all()), "model check: non-finite logits")
    check(err <= tol, f"model check: logits error {err} > {tol}")


# ---------------------------------------------------------------------------
# (d) FRAC checkpoint of served weights
# ---------------------------------------------------------------------------


def phase_checkpoint(ctx) -> None:
    """frac8 save + restore of the embedding and one stacked layer
    leaf (the whole 3.2B-parameter tree would spend most of the call
    on host transfers and disk)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.frac_pack import ops as fops
    from repro.train.checkpoint import CheckpointManager

    check(fops.default_mode(8) == "pallas",
          "frac8 checkpoint would not run the compiled kernel")
    params = ctx["params"]
    tree = {"embed": params["embed"],
            "wk": params["layers"]["attn_0"]["wk"]}
    root = ROOT / ".smoke_ckpt"
    shutil.rmtree(root, ignore_errors=True)
    try:
        mgr = CheckpointManager(str(root), mode="frac8")
        t0 = time.perf_counter()
        res = mgr.save(0, tree)
        t_save = time.perf_counter() - t0
        tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           tree)
        t0 = time.perf_counter()
        back, _ = mgr.restore(tpl)
        t_restore = time.perf_counter() - t0
    finally:
        shutil.rmtree(root, ignore_errors=True)
    n = sum(a.size for a in jax.tree.leaves(tree))
    log(f"[ckpt] frac8 leaves={list(tree)} params={n} "
        f"bytes_written={res.bytes_written} save_s={t_save:.2f} "
        f"restore_s={t_restore:.2f}")
    for name, leaf in tree.items():
        orig = np.asarray(leaf.astype(jnp.float32)).reshape(-1)
        got = np.asarray(back[name]).astype(np.float32).reshape(-1)
        check(back[name].shape == leaf.shape
              and back[name].dtype == leaf.dtype,
              f"ckpt {name}: shape/dtype not restored")
        # per 256-value block: |x - dequant(quant(x))| <= scale/q, plus
        # the bf16 rounding of the restored leaf (2^-8 relative)
        pad = (-orig.size) % 256
        blocks = np.pad(orig, (0, pad)).reshape(-1, 256)
        bound = np.abs(blocks).max(axis=1, keepdims=True) / 255.0
        err = np.abs(np.pad(got - orig, (0, pad)).reshape(-1, 256))
        worst = float((err - (bound * 1.01 + np.abs(
            np.pad(orig, (0, pad)).reshape(-1, 256)) * 2.0 ** -8)).max())
        log(f"[ckpt] {name} shape={leaf.shape} max_abs_err="
            f"{float(err.max()):.3e} bound_excess={worst:.3e}")
        check(worst <= 0.0, f"ckpt {name}: restore error above the "
              "frac8 quantizer bound")


# ---------------------------------------------------------------------------
# --chips 4: stablelm-12b on a (data=1, model=4) mesh
# ---------------------------------------------------------------------------


def phase_mesh(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve as launcher
    from repro.launch.mesh import make_host_mesh
    from repro.models import model
    from repro.sharding import rules

    mesh = make_host_mesh(data=1, model=4)
    dev = jax.devices()[0]
    label = f"{dev.platform}:{dev.device_kind} x{mesh.size}"
    full = get_config(STABLELM)
    shard = rules.param_shardings(model.param_specs(full), mesh)
    params, t_init = timed(model.init_params_jit, full,
                           jax.random.PRNGKey(ctx["seed"]), shard)
    per_dev = {d.id: d.memory_stats().get("bytes_in_use", 0)
               for d in mesh.devices.flat if d.memory_stats()}
    log(f"[mesh] {full.name} layers={full.num_layers} "
        f"params={sum(p.size for p in jax.tree.leaves(params))} "
        f"sharded init_s={t_init:.1f} bytes_in_use={per_dev}")
    rng = np.random.default_rng(ctx["seed"] + 6)
    lens, max_new = [512, 384, 256, 128], [32, 24, 16, 20]
    prompts = [rng.integers(1, full.vocab_size, n).astype(np.int32)
               for n in lens]
    t0 = time.perf_counter()
    eng, out = launcher.serve_requests(
        full, params, prompts, max_new, max_batch=4, paged=True,
        page_size=16, mesh=mesh)
    t_cold = time.perf_counter() - t0
    s0 = (eng.stats.tokens, eng.stats.host_syncs, eng.stats.decode_s,
          eng.stats.decode_steps)
    t0 = time.perf_counter()
    _, out2 = launcher.serve_requests(full, params, prompts, max_new,
                                      engine=eng)
    t_warm = time.perf_counter() - t0
    toks, syncs, dec_s, steps = _served_stats(eng, *s0)
    for (rid, o), m in zip(sorted(out.items()), max_new):
        check(len(o) == m, f"mesh request {rid}: {len(o)} tokens, wanted {m}")
    check(list(out.values()) == list(out2.values()),
          "mesh: warm serve of the same requests gave different tokens")
    log(f"[mesh] device={label} tokens_served={sum(map(len, out.values()))} "
        f"cold_wall_s={t_cold:.2f} warm_wall_s={t_warm:.2f} "
        f"compile_s~{t_cold - t_warm:.2f} host_syncs={syncs} "
        f"steady_decode_tokens_per_s={(toks - len(prompts)) / dec_s:.1f}")
    del params, eng

    # depth-cut copy, same widths: on the mesh vs on one of its devices
    cut = full.replace(name=f"{full.name}-2L", num_layers=2)
    shard = rules.param_shardings(model.param_specs(cut), mesh)
    p_mesh = model.init_params_jit(cut, jax.random.PRNGKey(ctx["seed"]),
                                   shard)
    one = jax.sharding.SingleDeviceSharding(mesh.devices.flat[0])
    p_one = jax.device_put(p_mesh, one)
    toks = jnp.asarray(rng.integers(1, cut.vocab_size, (2, 256)), jnp.int32)
    fwd = jax.jit(lambda p, t: model.forward(cut, p, {"tokens": t}))
    lm = fwd(p_mesh, jax.device_put(toks, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()))).astype(jnp.float32)
    l1 = fwd(p_one, jax.device_put(toks, one)).astype(jnp.float32)
    lm = jax.device_put(lm, one)
    scale = float(jnp.max(jnp.abs(l1)))
    err = float(jnp.max(jnp.abs(lm - l1)))
    agree = float(jnp.mean(jnp.argmax(lm, -1) == jnp.argmax(l1, -1)))
    # Tolerance: the sharded program sums each layer's TP partial
    # products across 4 devices (a different fp32 order, then a bf16
    # rounding of the all-reduced activation); over 2 layers that is a
    # few bf16 steps (2^-8 relative) of the logit scale.  2^-5 of the
    # largest |logit| leaves headroom and still catches a wrong shard.
    tol = 2.0 ** -5 * scale
    log(f"[mesh] depth-cut {cut.name} logits mesh-vs-one-device "
        f"max_abs_err={err:.4e} scale={scale:.4e} tol={tol:.4e} "
        f"top1_agreement={agree:.4f}")
    check(err <= tol, f"mesh depth-cut logits differ: {err} > {tol}")


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} "
              "device(s) attached", file=sys.stderr)
        return 1
    log(f"[smoke] compile cache: {cache_dir}")
    ctx = {"seed": args.seed}
    phases = [("device", phase_device)]
    if args.chips == 4:
        phases.append(("mesh", phase_mesh))
    else:
        phases += [("kernels", phase_kernels), ("serve", phase_serve),
                   ("model", model_check), ("ckpt", phase_checkpoint)]
    try:
        run_all(phases, ctx)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
