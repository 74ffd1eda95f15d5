"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --preset tiny --steps 50 --ckpt /tmp/run1 [--resume] \
        [--carbon-aware] [--grad-compress 8] [--snapshot frac8]

On a real multi-host TPU deployment this binary runs per host under
`jax.distributed.initialize()` with the production mesh
(launch/mesh.py); on this CPU container it runs the identical code path
on the host mesh.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import ARCH_IDS, get_config, get_tiny
from repro.core.power import traces
from repro.core.power.scheduler import CarbonAwareScheduler, SchedulerConfig
from repro.launch.cache import use_compile_cache
from repro.train.loop import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="/tmp/verdant_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--carbon-aware", action="store_true")
    ap.add_argument("--snapshot", default=None, choices=[None, "frac8", "frac4"])
    ap.add_argument("--grad-compress", type=int, default=16)
    args = ap.parse_args()

    use_compile_cache()
    mcfg = get_tiny(args.arch) if args.preset == "tiny" else get_config(args.arch)
    trace = None
    sch = None
    if args.carbon_aware:
        grid = traces.make_trace(days=2, seed=0)
        trace = traces.datacenter_supply(grid) / 30.0
        sch = CarbonAwareScheduler(SchedulerConfig(use_forecast=False))

    tcfg = TrainerConfig(
        total_steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every, lr=args.lr,
        snapshot_mode=args.snapshot, grad_compress_kbits=args.grad_compress,
        power_trace=trace, steps_per_power_interval=4,
        log_path=f"{args.ckpt}/metrics.jsonl",
    )
    out = Trainer(mcfg, tcfg, scheduler=sch).run()
    print(f"done: step={out['final_step']} loss={out['final_loss']:.4f} "
          f"paused={out['paused_steps']} stragglers={out['stragglers']}")


if __name__ == "__main__":
    main()
