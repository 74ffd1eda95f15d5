"""Serving launcher: continuous-batched requests against a checkpoint
(or seeded random weights for shape testing).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        [--preset tiny|full] [--seed 0] [--ckpt /tmp/run1] \
        --requests 8 --max-new 16 [--mixed-lengths] \
        [--paged] [--kv-frac-kbits 8]

``--preset full`` serves the published width and depth (one v5e chip
holds llama3.2-3b whole); ``tiny`` is the same family cut down for CPU.
``--mixed-lengths`` submits a spread of prompt lengths; families that
support ragged buckets (model.supports_ragged) then serve them through
one right-padded prefill per bucket instead of one bucket per length.
``--paged`` reads the KV pool through the page-walk attention
(kernels/paged_attn: the compiled Pallas kernel on a TPU, the jnp walk
elsewhere).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_tiny
from repro.launch.cache import use_compile_cache
from repro.models import model
from repro.serve.engine import ServeEngine


def load_params(mcfg, *, seed: int = 0, ckpt: str | None = None):
    """Weights from a checkpoint, else seeded random ones drawn on the
    device in their own dtype (no fp32 copy of the whole tree)."""
    if ckpt:
        from repro.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(ckpt)
        tpl = {"params": model.abstract_params(mcfg)}
        tree, _ = mgr.restore(tpl)
        return jax.tree.map(jax.numpy.asarray, tree["params"])
    return model.init_params_jit(mcfg, jax.random.PRNGKey(seed))


def serve_requests(mcfg, params, prompts, max_new, *, engine=None,
                   max_wall_s: float | None = None, **engine_kw):
    """Submit ``prompts`` (one max_new each, or one int for all) and
    serve them to completion.  Pass ``engine`` to reuse a warm one,
    else ``engine_kw`` build a ``ServeEngine``.  Returns
    (engine, {rid: tokens} for these requests)."""
    eng = engine or ServeEngine(mcfg, params, **engine_kw)
    if isinstance(max_new, int):
        max_new = [max_new] * len(prompts)
    rids = [eng.submit(p, max_new_tokens=m, max_wall_s=max_wall_s)
            for p, m in zip(prompts, max_new)]
    out = eng.run()
    return eng, {r: out[r] for r in rids}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--seed", type=int, default=0,
                    help="random-weight and prompt seed (no --ckpt)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="spread prompt lengths across requests "
                         "(exercises ragged buckets where supported)")
    ap.add_argument("--kv-frac-kbits", type=int, default=None,
                    help="FRAC-quantize the KV cache at this bit width")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool + in-loop admission "
                         "(falls back to contiguous for families "
                         "without an appendable KV cache)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV slots per page in --paged mode")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="decode lanes per bucket (fewer lanes + more "
                         "requests = more staging/oversubscription)")
    ap.add_argument("--flash-oversubscribe", action="store_true",
                    help="oversubscribe the paged pool with a simulated "
                         "recycled-flash spill tier (requires --paged)")
    ap.add_argument("--flash-blocks", type=int, default=64,
                    help="blocks in the simulated recycled chip")
    ap.add_argument("--flash-seed", type=int, default=0,
                    help="pre-wear / fault-injection seed")
    ap.add_argument("--flash-rber-scale", type=float, default=1.0,
                    help="scale organic flash RBER (0 disables faults)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall deadline; expired requests "
                         "return whatever they produced")
    args = ap.parse_args(argv)

    use_compile_cache()
    mcfg = get_tiny(args.arch) if args.preset == "tiny" \
        else get_config(args.arch)
    params = load_params(mcfg, seed=args.seed, ckpt=args.ckpt)

    flash = None
    if args.flash_oversubscribe:
        from repro.core.frac.wear import RecycledChip
        from repro.serve.faults import FaultConfig
        from repro.serve.flash_tier import FlashTier

        flash = FlashTier(
            RecycledChip(n_blocks=args.flash_blocks, seed=args.flash_seed),
            faults=FaultConfig(seed=args.flash_seed,
                               rber_scale=args.flash_rber_scale))
    rng = np.random.default_rng(args.seed)
    prompts = []
    for i in range(args.requests):
        plen = args.prompt_len
        if args.mixed_lengths:
            plen = max(2, args.prompt_len - (i % 4) * 2)
        prompts.append(rng.integers(1, mcfg.vocab_size, plen).astype(np.int32))
    eng, out = serve_requests(
        mcfg, params, prompts, args.max_new, max_wall_s=args.deadline_s,
        max_batch=args.max_batch, kv_frac_kbits=args.kv_frac_kbits,
        paged=args.paged, page_size=args.page_size,
        paged_kernel=args.paged, flash=flash)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    for rid, toks in out.items():
        print(f"req {rid}: {toks}")
    s = eng.stats
    rep = eng.energy_report()
    wall = sum(r.latency_s for r in eng.reports.values())
    tps = s.tokens / wall if wall else float("inf")
    ttft = 1e3 * float(np.mean(s.ttft_s)) if s.ttft_s else 0.0
    print(f"requests={s.requests} prefills={s.prefills} "
          f"decode_steps={s.decode_steps} tokens={s.tokens} "
          f"host_syncs={s.host_syncs}")
    print(f"tokens/s={tps:.1f} mean_ttft_ms={ttft:.1f} "
          f"J/token={rep.operational_j / max(s.tokens, 1):.3f} "
          f"ragged={'yes' if model.supports_ragged(mcfg) else 'no'}")
    if s.kv_bytes_frac:
        print(f"kv_bytes: full={s.kv_bytes_full} frac={s.kv_bytes_frac} "
              f"({s.kv_bytes_full / s.kv_bytes_frac:.2f}x)")
    if eng.paged:
        print(f"paged: page_size={eng.page_size} "
              f"pages_peak={s.kv_pages_peak} "
              f"kv_bytes_peak={s.kv_bytes_peak} "
              f"kv_bytes_pool={s.kv_bytes_pool} "
              f"in_loop_admissions={s.admissions}")
    elif args.paged:
        print("paged: requested but family has no appendable KV cache "
              "— served contiguous")
    if flash is not None:
        fd = rep.detail.get("flash", {})
        print(f"flash: waves={s.oversub_waves} spills={s.spills} "
              f"faultins={s.faultins} ecc={s.ecc_corrected} "
              f"retries={s.retry_reads} reprefills={s.reprefills} "
              f"bytes_peak={s.flash_bytes_peak} "
              f"io={fd.get('reads', 0)}r/{fd.get('writes', 0)}w/"
              f"{fd.get('erases', 0)}e op_j={fd.get('op_j', 0.0):.2e} "
              f"capacity_left={flash.capacity_bytes():.0f}B")
    if s.timeouts:
        print(f"deadlines: {s.timeouts} request(s) expired at "
              f"--deadline-s={args.deadline_s}")


if __name__ == "__main__":
    main()
